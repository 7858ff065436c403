"""Tests for the hundreds-of-tenants scaling paths added with the
batched tick engine:

  * `BatchedLinkSim` — T tenants in one jitted call must match T
    independent `AdaptiveLinkSim` instances state-for-state across mixed
    cadences and inactive-tenant masks;
  * the engine's batched-tick mode — conservation, determinism, and the
    auto flag defaulting on only where equivalence is proven;
  * the closed-form 'none' strategy — bit-exact vs the event loop in the
    proven regime, eligibility gating;
  * `sim/replay.py` pool regressions — a poisoned process pool must
    recover on the next `_map_queries` call, and `warm_pool` must surface
    worker crashes instead of discarding its futures.
"""

import warnings
from concurrent.futures import Future

import numpy as np
import pytest

import repro.sim.replay as replay
from repro.core.types import DySkewConfig, Policy, SkewModelKind
from repro.sim.batched_link import BatchedLinkSim, _next_pow2
from repro.sim.engine import (
    AdaptiveLinkSim,
    ClusterConfig,
    MultiQuerySimulator,
    StrategyConfig,
    TenantQuery,
    _arrivals_on_grid,
    closed_form_none_result,
)
from repro.sim.replay import dyskew_strategy, scan_arrival_gap, staggered_tenants
from repro.sim.workload import QueryProfile, generate_query, multi_tenant_suite


def _tree_leaves(state):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]


def _rand_inputs(rng, n):
    rows = (rng.poisson(3, n) * (rng.random(n) < 0.7)).astype(np.float64)
    sync = rng.random(n) * rows
    density = rng.random(n) * 100.0
    bpr = rng.random(n) * 2e6
    signal = rng.random(n) < 0.3
    return rows, sync, density, bpr, signal


CONFIGS = [
    DySkewConfig(policy=Policy.EAGER_SNOWPARK,
                 skew_model=SkewModelKind.IDLE_TIME, n_strikes=2),
    DySkewConfig(policy=Policy.LATE,
                 skew_model=SkewModelKind.ROW_PERCENTAGE, n_strikes=3),
    DySkewConfig(policy=Policy.LATE,
                 skew_model=SkewModelKind.SYNC_TIME_SLOPE, n_strikes=2),
]


class TestBatchedLinkSim:
    @pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.skew_model.name)
    def test_matches_independent_instances_mixed_cadence(self, cfg):
        """T tenants ticking on DIFFERENT cadences (via the active mask)
        must match T independent AdaptiveLinkSim instances state-for-state
        and mask-for-mask at every step."""
        n, T, steps = 6, 5, 40
        rng = np.random.default_rng(0)
        batched = BatchedLinkSim(cfg, n, T)
        solo = [AdaptiveLinkSim(cfg, n) for _ in range(T)]
        # Tenant t ticks every (t+1)-th step — mixed cadences.
        for step in range(steps):
            active = np.array([step % (t + 1) == 0 for t in range(T)])
            inputs = [_rand_inputs(rng, n) for _ in range(T)]
            stacked = [np.stack([inp[k] for inp in inputs])
                       for k in range(5)]
            dist = batched.tick(*stacked, active=active)
            for t in range(T):
                if active[t]:
                    d = solo[t].tick(*(np.asarray(x) for x in inputs[t]))
                    np.testing.assert_array_equal(dist[t], d)
                else:
                    assert not dist[t].any()
        for t in range(T):
            for a, b in zip(_tree_leaves(solo[t].state),
                            _tree_leaves(batched.state)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)[t],
                    err_msg=f"tenant {t} state leaf diverged",
                )

    def test_inactive_rows_frozen(self):
        cfg = CONFIGS[0]
        n, T = 4, 3
        rng = np.random.default_rng(1)
        sim = BatchedLinkSim(cfg, n, T)
        before = [x.copy() for x in _tree_leaves(sim.state)]
        inputs = [np.stack([_rand_inputs(rng, n)[k]] * T) for k in range(5)]
        sim.tick(*inputs, active=np.zeros(T, bool))
        after = _tree_leaves(sim.state)
        for a, b in zip(before, after):
            np.testing.assert_array_equal(a, b)

    def test_capacity_padding(self):
        assert _next_pow2(1) == 1
        assert _next_pow2(2) == 2
        assert _next_pow2(129) == 256
        sim = BatchedLinkSim(CONFIGS[0], 4, 5)
        assert sim.capacity == 8
        assert sim.states.shape == (5, 4)


class TestBatchedEngineMode:
    def _tenants(self, cluster, num=6, seed=43):
        profiles = multi_tenant_suite(num, seed=seed)
        return staggered_tenants(profiles, cluster, dyskew_strategy, seed=0)

    def test_batched_conserves_and_is_deterministic(self):
        cluster = ClusterConfig(num_nodes=2)
        tenants = self._tenants(cluster)
        r1 = MultiQuerySimulator(cluster, batch_ticks=True).run(tenants)
        r2 = MultiQuerySimulator(cluster, batch_ticks=True).run(
            self._tenants(cluster)
        )
        for t, r in zip(tenants, r1):
            total = sum(b.costs.sum() for s in t.streams for b in s)
            np.testing.assert_allclose(r.per_worker_busy.sum(), total,
                                       rtol=1e-9)
        for a, b in zip(r1, r2):
            assert a.latency == b.latency
            assert a.rows_redistributed == b.rows_redistributed

    def test_single_link_tenant_auto_equals_per_tenant(self):
        """The auto default (batch when at most one tenant has a link)
        must be bit-identical to the forced per-tenant path."""
        cluster = ClusterConfig(num_nodes=2)
        prof = QueryProfile(
            name="auto", n_rows=1500, mean_row_cost=1e-3, cost_sigma=1.0,
            partition_alpha=0.8, hot_fraction=0.2,
        )
        batches = generate_query(prof, cluster.num_workers, seed=7)
        gap = scan_arrival_gap(prof, cluster)
        st = dyskew_strategy(prof)
        t = [TenantQuery("solo", batches, st, 0.0, gap)]
        auto = MultiQuerySimulator(cluster).run(t)[0]
        per = MultiQuerySimulator(cluster, batch_ticks=False).run(t)[0]
        assert auto.latency == per.latency
        assert auto.num_ticks == per.num_ticks
        np.testing.assert_array_equal(auto.per_worker_busy,
                                      per.per_worker_busy)

    def test_batched_groups_by_config(self):
        """Tenants with different (config, cadence) still run correctly
        under forced batching (one group per distinct key)."""
        cluster = ClusterConfig(num_nodes=2)
        profiles = multi_tenant_suite(4, seed=41)
        tenants = staggered_tenants(profiles, cluster, dyskew_strategy,
                                    seed=0)
        tenants[1].strategy = StrategyConfig(
            kind="dyskew",
            dyskew=DySkewConfig(policy=Policy.LATE,
                                skew_model=SkewModelKind.ROW_PERCENTAGE),
            tick_interval=25e-3,
        )
        results = MultiQuerySimulator(cluster, batch_ticks=True).run(tenants)
        for t, r in zip(tenants, results):
            total = sum(b.costs.sum() for s in t.streams for b in s)
            np.testing.assert_allclose(r.per_worker_busy.sum(), total,
                                       rtol=1e-9)


class TestAutoEnvelope:
    """The widened batched-tick auto envelope: a multi-link (config,
    cadence) group batches by default when every member arrival lies
    exactly on the group's chained tick grid — identical arrivals being
    the trivial case — and falls back to per-tenant links otherwise."""

    def test_on_grid_detection(self):
        assert _arrivals_on_grid([0.3, 0.3, 0.3], 0.05)  # identical
        # Chained grid values (the engine walks t += I, so must we).
        t, grid = 0.1, [0.1]
        for _ in range(5):
            t += 0.008
            grid.append(t)
        assert _arrivals_on_grid([grid[0], grid[2], grid[5]], 0.008)
        assert not _arrivals_on_grid([0.1, 0.1037], 0.008)  # off grid
        # Exactness matters: k*I need not equal the chained sum.
        assert not _arrivals_on_grid([0.0, 0.1 + 3e-18], 0.008)

    def _identical_arrival_tenants(self, cluster, arrival=0.02):
        profiles = multi_tenant_suite(4, seed=51)
        tenants = staggered_tenants(profiles, cluster, dyskew_strategy,
                                    seed=2)
        for t in tenants:
            t.arrival = arrival
        return tenants

    def test_identical_arrival_group_auto_batches_bit_exact(self):
        """Identical-arrival multi-link tenants: the auto default must
        take the batched path AND reproduce the per-tenant trajectory
        bit-for-bit (ticks, latencies, busy vectors)."""
        cluster = ClusterConfig(num_nodes=2)
        tenants = self._identical_arrival_tenants(cluster)
        assert sum(t.strategy.kind == "dyskew" for t in tenants) > 1
        auto = MultiQuerySimulator(cluster).run(tenants)
        per = MultiQuerySimulator(cluster, batch_ticks=False).run(
            self._identical_arrival_tenants(cluster)
        )
        forced = MultiQuerySimulator(cluster, batch_ticks=True).run(
            self._identical_arrival_tenants(cluster)
        )
        for a, p, f in zip(auto, per, forced):
            assert a.latency == p.latency == f.latency
            assert a.num_ticks == p.num_ticks == f.num_ticks
            np.testing.assert_array_equal(a.per_worker_busy,
                                          p.per_worker_busy)
            np.testing.assert_array_equal(a.per_worker_busy,
                                          f.per_worker_busy)

    def test_off_grid_group_falls_back_per_tenant(self):
        """Scattered arrivals: auto must keep the per-tenant cadence
        (the shared grid would quantize tick times)."""
        cluster = ClusterConfig(num_nodes=2)
        profiles = multi_tenant_suite(4, seed=51)
        tenants = staggered_tenants(profiles, cluster, dyskew_strategy,
                                    seed=2)
        arrivals = [t.arrival for t in tenants if
                    t.strategy.kind == "dyskew"]
        interval = tenants[0].strategy.tick_interval
        assert not _arrivals_on_grid(arrivals, interval)
        auto = MultiQuerySimulator(cluster).run(tenants)
        per = MultiQuerySimulator(cluster, batch_ticks=False).run(
            staggered_tenants(profiles, cluster, dyskew_strategy, seed=2)
        )
        for a, p in zip(auto, per):
            assert a.latency == p.latency
            assert a.num_ticks == p.num_ticks

    def test_grid_aligned_open_loop_batches_by_default(self):
        """`open_loop_tenants(grid_align=I)` snaps a whole open-loop
        fleet onto the tick grid, so `many_tenants_suite`-style traffic
        rides the batched path under the auto default, bit-identically."""
        from repro.sim.replay import open_loop_tenants
        from repro.sim.workload import ArrivalProcess, many_tenants_suite

        cluster = ClusterConfig(num_nodes=1)
        specs = many_tenants_suite(16, seed=71)
        st = StrategyConfig(
            kind="dyskew",
            dyskew=DySkewConfig(policy=Policy.LATE,
                                skew_model=SkewModelKind.IDLE_TIME),
            tick_interval=8e-3,
        )
        proc = ArrivalProcess(kind="poisson", rate=30.0)
        tenants = open_loop_tenants(
            specs, cluster, lambda prof: st, proc, 16, seed=3,
            grid_align=st.tick_interval,
        )
        assert _arrivals_on_grid([t.arrival for t in tenants],
                                 st.tick_interval)
        auto = MultiQuerySimulator(cluster).run(tenants)
        per = MultiQuerySimulator(cluster, batch_ticks=False).run(tenants)
        for a, p in zip(auto, per):
            assert a.latency == p.latency
            assert a.num_ticks == p.num_ticks
            np.testing.assert_array_equal(a.per_worker_busy,
                                          p.per_worker_busy)

    def test_batched_waterfill_engages_on_same_time_arrivals(self):
        """An eager tenant's producers all arrive at one instant — the
        coalesced run must route them through `waterfill_counts_many`."""
        cluster = ClusterConfig(num_nodes=2)
        prof = QueryProfile(
            name="wf", n_rows=1200, mean_row_cost=1e-3, cost_sigma=1.0,
            partition_alpha=0.6, hot_fraction=0.2,
        )
        batches = generate_query(prof, cluster.num_workers, seed=13)
        st = StrategyConfig(
            kind="dyskew",
            dyskew=DySkewConfig(policy=Policy.EAGER_SNOWPARK),
        )
        sim = MultiQuerySimulator(cluster)
        sim.run([TenantQuery("wf", batches, st, 0.0,
                             scan_arrival_gap(prof, cluster))])
        counts = sim.last_event_counts
        assert counts["arrival_runs_coalesced"] >= 1
        assert counts["waterfill_batched_rows"] >= 1


class TestClosedFormNone:
    def _single_batch_tenant(self, cluster, seed=3, arrival=0.0):
        prof = QueryProfile(name="cf", n_rows=400, mean_row_cost=1e-3,
                            cost_sigma=0.9, batch_rows=10_000)
        batches = generate_query(prof, cluster.num_workers, seed=seed)
        assert all(len(s) <= 1 for s in batches)
        return TenantQuery("cf", batches, StrategyConfig(kind="none"),
                           arrival, 1e-4)

    def test_exact_vs_event_loop_single_batch(self):
        cluster = ClusterConfig(num_nodes=2)
        t = self._single_batch_tenant(cluster)
        loop = MultiQuerySimulator(cluster, none_closed_form=False).run([t])[0]
        cf = closed_form_none_result(t, cluster)
        assert cf.latency == loop.latency
        assert cf.utilization == loop.utilization
        np.testing.assert_array_equal(cf.per_worker_busy,
                                      loop.per_worker_busy)
        assert cf.num_ticks == 0 and cf.rows_redistributed == 0

    def test_auto_takes_closed_form_only_when_proven(self):
        cluster = ClusterConfig(num_nodes=2)
        t = self._single_batch_tenant(cluster)
        auto = MultiQuerySimulator(cluster).run([t])[0]
        cf = closed_form_none_result(t, cluster)
        assert auto.latency == cf.latency
        # Multi-batch streams: auto must stay on the event loop.
        prof = QueryProfile(name="mb", n_rows=2000, mean_row_cost=1e-3,
                            cost_sigma=0.9)
        batches = generate_query(prof, cluster.num_workers, seed=3)
        assert any(len(s) > 1 for s in batches)
        tm = TenantQuery("mb", batches, StrategyConfig(kind="none"),
                         0.0, scan_arrival_gap(prof, cluster))
        auto_m = MultiQuerySimulator(cluster).run([tm])[0]
        loop_m = MultiQuerySimulator(
            cluster, none_closed_form=False).run([tm])[0]
        assert auto_m.latency == loop_m.latency

    def test_overlapping_producers_ineligible(self):
        """Two 'none' tenants on the SAME producers share worker FIFOs —
        the closed form must refuse even when forced."""
        cluster = ClusterConfig(num_nodes=2)
        a = self._single_batch_tenant(cluster, seed=3)
        b = self._single_batch_tenant(cluster, seed=4)
        sim = MultiQuerySimulator(cluster, none_closed_form=True)
        assert not sim._none_fast_path_ok([a, b])
        loop = MultiQuerySimulator(cluster, none_closed_form=False)
        res_forced = sim.run([a, b])
        res_loop = loop.run([a, b])
        for x, y in zip(res_forced, res_loop):
            assert x.latency == y.latency

    def test_disjoint_tenants_exact(self):
        cluster = ClusterConfig(num_nodes=2)
        n = cluster.num_workers
        prof = QueryProfile(name="dj", n_rows=600, mean_row_cost=1e-3,
                            cost_sigma=0.8, batch_rows=10_000)
        full = generate_query(prof, n, seed=9)
        st = StrategyConfig(kind="none")
        half = n // 2
        ta = TenantQuery("a", [s if p < half else [] for p, s in
                               enumerate(full)], st, 0.0, 1e-4)
        tb = TenantQuery("b", [s if p >= half else [] for p, s in
                               enumerate(full)], st, 0.05, 1e-4)
        fast = MultiQuerySimulator(cluster).run([ta, tb])
        loop = MultiQuerySimulator(cluster, none_closed_form=False).run(
            [ta, tb]
        )
        for x, y in zip(fast, loop):
            assert x.latency == y.latency
            np.testing.assert_array_equal(x.per_worker_busy,
                                          y.per_worker_busy)


# ------------------------------------------------------------------ #
# replay.py pool regressions
# ------------------------------------------------------------------ #


class _FailingPool:
    """Executor stub whose map always raises (a poisoned pool)."""

    def __init__(self):
        self.shutdowns = []

    def map(self, *a, **kw):
        raise RuntimeError("worker died")

    def shutdown(self, wait=True, cancel_futures=False):
        self.shutdowns.append((wait, cancel_futures))


class _InProcessPool:
    """Executor stub that runs map in-process (a healthy pool)."""

    def map(self, fn, tasks, chunksize=1):
        return [fn(t) for t in tasks]

    def shutdown(self, wait=True, cancel_futures=False):
        pass


def _tiny_tasks(k=2):
    cluster = ClusterConfig(num_nodes=1, interpreters_per_node=4)
    prof = QueryProfile(name="tiny", n_rows=64, mean_row_cost=1e-4,
                        cost_sigma=0.3)
    st = StrategyConfig(kind="none")
    return [(prof, cluster, st, i, i, 1e-4) for i in range(k)]


class TestPoolRecovery:
    def setup_method(self):
        self._saved = (replay._POOL, replay._POOL_WORKERS)
        replay._POOL, replay._POOL_WORKERS = None, 0

    def teardown_method(self):
        replay._POOL, replay._POOL_WORKERS = self._saved

    def test_poisoned_pool_recovers_on_next_call(self, monkeypatch):
        """Regression: one pool failure used to permanently degrade
        _map_queries to serial (the broken executor stayed cached)."""
        bad = _FailingPool()
        replay._POOL, replay._POOL_WORKERS = bad, 8
        tasks = _tiny_tasks()
        with pytest.warns(RuntimeWarning, match="pool failed"):
            results = replay._map_queries(tasks, workers=8)
        assert len(results) == len(tasks)  # serial fallback still ran
        # The broken pool was shut down and discarded...
        assert replay._POOL is None and replay._POOL_WORKERS == 0
        assert bad.shutdowns
        # ...so the next call builds a fresh pool and uses it.
        good = _InProcessPool()
        monkeypatch.setattr(replay, "_get_pool", lambda workers: good)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            results2 = replay._map_queries(tasks, workers=8)
        assert len(results2) == len(tasks)
        for a, b in zip(results, results2):
            assert a.latency == b.latency

    def test_grow_shuts_replaced_pool_down_waiting(self, monkeypatch):
        """Growing the pool must reap the replaced pool's processes
        (shutdown wait=True), not leak them."""
        small = _FailingPool()
        replay._POOL, replay._POOL_WORKERS = small, 2

        created = []

        class _FakeExecutor(_InProcessPool):
            def __init__(self, max_workers=None, mp_context=None):
                created.append(max_workers)

        monkeypatch.setattr(replay, "ProcessPoolExecutor", _FakeExecutor)
        pool = replay._get_pool(4)
        assert isinstance(pool, _FakeExecutor) and created == [4]
        assert small.shutdowns == [(True, False)]

    def test_warm_pool_surfaces_worker_crash(self, monkeypatch):
        """Regression: warm_pool discarded its futures, so a worker that
        crashed during the jax warm-import was silently ignored."""

        class _CrashingSubmitPool:
            def submit(self, fn):
                f = Future()
                f.set_exception(RuntimeError("import jax segfaulted"))
                return f

        monkeypatch.setattr(
            replay, "_get_pool", lambda workers: _CrashingSubmitPool()
        )
        with pytest.warns(RuntimeWarning, match="warm-up worker failed"):
            futures = replay.warm_pool(workers=3)
        assert len(futures) == 3
        assert all(f.exception() is not None for f in futures)

    def test_warm_pool_quiet_on_success(self, monkeypatch):
        class _OkPool:
            def submit(self, fn):
                f = Future()
                f.set_result(True)
                return f

        monkeypatch.setattr(replay, "_get_pool", lambda workers: _OkPool())
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            futures = replay.warm_pool(workers=2)
        assert [f.result() for f in futures] == [True, True]

    def test_accelerator_backend_starts_no_pool(self, monkeypatch):
        """Off the CPU backend the parent holds the chip: no worker
        process may be started, and the suite runs in-process."""

        def _no_pool(workers):
            raise AssertionError("a worker pool was requested")

        monkeypatch.setattr(replay, "_get_pool", _no_pool)
        monkeypatch.setattr(replay.jax, "default_backend", lambda: "tpu")
        tasks = _tiny_tasks()
        with pytest.warns(RuntimeWarning, match="one process per chip"):
            assert replay.warm_pool(workers=4) == []
        with pytest.warns(RuntimeWarning, match="one process per chip"):
            results = replay._map_queries(tasks, workers=4)
        serial = replay._map_queries(tasks, workers=None)
        assert [r.latency for r in results] == [r.latency for r in serial]

    @pytest.mark.parametrize("workers,want", [(None, 0), (0, 0), (1, 0),
                                              (6, 6)])
    def test_cpu_backend_keeps_pool_size(self, workers, want):
        assert replay.pool_workers(workers) == want
