"""Additional edge-case coverage: adaptive-link telemetry, serving
migration behavior, data-pipeline permutation invariants, report
rendering, launcher configs."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AdaptiveLink, AdaptiveLinkConfig, DySkewConfig, Policy
from repro.data.pipeline import DataConfig, DataPipeline
from repro.roofline.report import fmt_bytes, fmt_s, roofline_table
from repro.serving.engine import Request, ServeConfig, ServingEngine


class TestAdaptiveLinkTelemetry:
    def test_cost_gate_telemetry_fields(self):
        link = AdaptiveLink(AdaptiveLinkConfig(
            dyskew=DySkewConfig(policy=Policy.EAGER_SNOWPARK),
            num_instances=4,
        ))
        state = link.init_state()
        state, plan = link.step(
            state, jnp.ones(16), jnp.full(16, 1e3), jnp.zeros(16, jnp.int32)
        )
        assert float(plan.est_bytes_moved) > 0
        assert float(plan.est_time_saved) > 0

    def test_transitions_counted_once_per_commit(self):
        link = AdaptiveLink(AdaptiveLinkConfig(
            dyskew=DySkewConfig(policy=Policy.EAGER_SNOWPARK),
            num_instances=2,
        ))
        state = link.init_state()
        for _ in range(5):
            state, _ = link.step(
                state, jnp.ones(8), jnp.ones(8), jnp.zeros(8, jnp.int32)
            )
        assert np.asarray(state["transitions"]).max() == 1


class TestServingMigration:
    def test_skewed_queues_trigger_migration(self):
        """A burst landing on one replica (all arrivals before the others
        spin up) must be spread by the DySkew rebalance pass."""
        cfg = ServeConfig(num_replicas=4, scheduler="dyskew",
                          kv_bytes_per_token=1e3)  # tiny KV → cheap to move
        # Long-running requests arriving simultaneously: least-loaded
        # placement ties are broken to replica 0 first.
        reqs = [
            Request(rid=i, prompt_len=64, max_new_tokens=500, arrival=0.0)
            for i in range(32)
        ]
        res = ServingEngine(cfg).run(reqs)
        assert res["completed"] == 32

    def test_round_robin_spreads_placement(self):
        cfg = ServeConfig(num_replicas=4, scheduler="round_robin")
        reqs = [Request(rid=i, prompt_len=64, max_new_tokens=10,
                        arrival=0.0) for i in range(8)]
        res = ServingEngine(cfg).run(reqs)
        assert res["completed"] == 8
        assert res["migrations"] == 0  # rr never migrates


class TestDataPipelinePermutation:
    def test_dyskew_reorder_preserves_sequences(self):
        """Balancing may permute rows across shards but must not create or
        destroy tokens."""
        cfg = DataConfig(vocab_size=100, seq_len=64, global_batch=8,
                         num_shards=4, dyskew_balance=True, seed=3)
        pipe = DataPipeline(cfg)
        b = next(pipe)
        cfg2 = DataConfig(vocab_size=100, seq_len=64, global_batch=8,
                          num_shards=4, dyskew_balance=False, seed=3)
        b2 = next(DataPipeline(cfg2))
        # same multiset of row-hashes regardless of balancing
        h1 = sorted(hash(r.tobytes()) for r in b["tokens"])
        h2 = sorted(hash(r.tobytes()) for r in b2["tokens"])
        assert h1 == h2


class TestReportRendering:
    def test_skip_rows_render(self):
        recs = [
            {"arch": "a", "shape": "long_500k", "mesh": "single",
             "status": "SKIP: quadratic"},
            {"arch": "a", "shape": "train_4k", "mesh": "single",
             "status": "OK",
             "roofline": {
                 "t_compute_s": 1.0, "t_memory_s": 2.0,
                 "t_collective_s": 0.5, "bottleneck": "memory",
                 "useful_flops_ratio": 0.7,
                 "collective_bytes_global": 1e12,
             },
             "memory": {"per_device_total_gb": 1.5, "fits_hbm": True}},
        ]
        table = roofline_table(recs, "single")
        assert "SKIP" in table and "memory" in table

    def test_formatters(self):
        assert fmt_s(0) == "0"
        assert fmt_s(5e-6).endswith("µs")
        assert fmt_s(0.005).endswith("ms")
        assert fmt_bytes(2e12) == "2.0TB"
        assert fmt_bytes(512) == "512B"


class TestLauncherConfigs:
    def test_all_archs_have_reduced_variants(self):
        from repro.config.base import all_arch_ids, get_config

        for a in all_arch_ids():
            r = get_config(a).reduced()
            assert r.d_model <= 128
            assert r.vocab_size <= 512
            if r.moe:
                # dropless in reduced mode (capacity covers worst case)
                assert r.moe.capacity_factor >= r.moe.num_experts / r.moe.top_k

    def test_dryrun_import_leaves_xla_flags(self, monkeypatch):
        """Only dryrun's main() adds its host-device flag; importing the
        module (as the test below does) must not touch XLA_FLAGS."""
        import importlib

        import repro.launch.dryrun as dryrun

        monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
        importlib.reload(dryrun)
        assert os.environ["XLA_FLAGS"] == "--xla_dump_to=/dev/null"

    def test_perf_flag_parsing(self):
        from repro.launch.dryrun import parse_flags

        flags, h7, h6 = parse_flags("h1,h5,h7")
        assert flags.causal_skip and flags.constrain_activations
        assert h7 and not h6
        flags, h7, h6 = parse_flags("")
        assert not flags.causal_skip and not h7


class TestCompileCache:
    """The entry points' cache rule: the environment's directory wins;
    otherwise one fixed directory inside the checkout."""

    @pytest.fixture(autouse=True)
    def _restore_config(self):
        import jax

        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs")
        saved = {k: getattr(jax.config, k) for k in keys}
        yield
        for k, v in saved.items():
            jax.config.update(k, v)

    def test_environment_directory_is_left_to_jax(self, monkeypatch, tmp_path):
        import jax

        from repro.runtime.compile_cache import configure_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert configure_compile_cache(str(tmp_path)) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_default_is_fixed_directory_in_checkout(self, monkeypatch, tmp_path):
        import jax

        from repro.runtime.compile_cache import configure_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(tmp_path / ".jax_cache")
        assert configure_compile_cache(str(tmp_path)) == want
        assert configure_compile_cache(str(tmp_path)) == want
        assert jax.config.jax_compilation_cache_dir == want
