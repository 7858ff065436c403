"""The benchmark's copies of the traffic generators and strategy rules
give, for a seed, exactly what the program's originals give; a
population given as a data file gives what its suite gives, and a
malformed one is refused."""

import copy
import dataclasses
import hashlib
import json
import os
import time

import numpy as np
import pytest

from bench import gen, harness, program_io, strategy

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def _traffic(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _same_profile(copy, orig):
    want = dataclasses.asdict(orig)
    want["policy"] = int(want["policy"])
    return dataclasses.asdict(copy) == want


def test_customer_replay_suite_matches():
    from repro.sim.workload import customer_replay_suite

    copies = gen.customer_replay_suite(150, 7)
    origs = customer_replay_suite(num_queries=150, seed=7)
    assert len(copies) == len(origs) == 150
    assert all(_same_profile(c, o) for c, o in zip(copies, origs))


@pytest.mark.parametrize("which,seed", [
    (0, 0), (3, 7_000_001), (41, 4_294_967_311 * 1000 + 5), (77, 123)])
def test_generate_query_matches(which, seed):
    from repro.sim.workload import customer_replay_suite, generate_query

    orig_prof = customer_replay_suite(num_queries=150, seed=7)[which]
    copy_prof = gen.customer_replay_suite(150, 7)[which]
    want = generate_query(orig_prof, 64, seed)
    got = gen.generate_query(copy_prof, 64, seed)
    assert len(got) == len(want)
    for gs, ws in zip(got, want):
        assert len(gs) == len(ws)
        for (c, s), b in zip(gs, ws):
            assert np.array_equal(c, b.costs) and np.array_equal(s, b.sizes)


def test_dyskew_rules_match_replay_dyskew_strategy():
    from repro.sim.replay import dyskew_strategy
    from repro.sim.workload import customer_replay_suite

    traffic = _traffic("dyskew")
    origs = customer_replay_suite(num_queries=150, seed=7)
    copies = gen.customer_replay_suite(150, 7)
    assert any(c.locality_constrained for c in copies)
    for c, o in zip(copies, origs):
        assert program_io.strategy(strategy.resolve(traffic, c)) == dyskew_strategy(o)


def test_scan_gaps_match_replay():
    from repro.sim.engine import ClusterConfig
    from repro.sim.replay import scan_arrival_gap
    from repro.sim.workload import customer_replay_suite

    cluster = ClusterConfig(num_nodes=8, interpreters_per_node=8)
    origs = customer_replay_suite(num_queries=150, seed=7)
    copies = gen.customer_replay_suite(150, 7)
    assert all(scan_arrival_gap(o, cluster) == gen.scan_arrival_gap(c, 64)
               for o, c in zip(origs, copies))


# --------------------------------------------------------------------- #
# Populations given as data files
# --------------------------------------------------------------------- #

POLICY_NAMES = {v: k for k, v in gen.POLICY_IDS.items()}


def _traffic_digest(g):
    """One hash of a generated cell: every job's profile names, arrivals,
    gaps, every stream's costs and sizes, and the strategies, of the
    window's pool and of the warm-up."""
    h = hashlib.sha256()
    for jobs, strats in ((g["pool"], g["strategies"]),
                         (g["warm"], g["warm_strategies"])):
        for job in jobs:
            for q in job:
                h.update(q.profile.name.encode())
                h.update(repr((q.arrival, q.gap)).encode())
                for stream in q.streams:
                    h.update(b"|")
                    for c, s in stream:
                        h.update(c.tobytes())
                        h.update(s.tobytes())
                        h.update(b";")
        h.update(json.dumps(strats, sort_keys=True).encode())
    return h.hexdigest()


@pytest.mark.parametrize("seed,digest", [
    (2_147_483_659,
     "af4a8f636c08056196589651b928c89917c515a5b778d7bdadf5b9fd819987f3"),
    (2**32 + 17,
     "0a7c357709d1df9485477e768a206900231e55c1e49431bb80e230e1ffbf81f3"),
])
def test_fig3_traffic_is_pinned(seed, digest):
    """`fig3.dyskew`'s whole generated traffic, at full size, hashes as it
    did before configurations could name a population file."""
    g = harness.generate(harness.load_cell("fig3.dyskew"), seed)
    assert len(g["pool"]) == 900 and len(g["warm"]) == 2
    assert _traffic_digest(g) == digest


def _profile_rows(profiles):
    rows = []
    for p in profiles:
        row = dataclasses.asdict(p)
        row["policy"] = POLICY_NAMES[row["policy"]]
        rows.append(row)
    return rows


def _write_population(directory, name, rows):
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, name + ".json"), "w") as f:
        json.dump({"about": "test population", "source": "test",
                   "profiles": rows}, f)


@pytest.mark.parametrize("seed", [2_147_483_659, 2**32 + 17])
def test_population_file_gives_the_suite_traffic(seed, tmp_path):
    suite_cell = harness.load_cell("fig3.dyskew")
    suite_cell["traffic"]["passes"] = 1
    _write_population(str(tmp_path), "cust",
                      _profile_rows(gen.customer_replay_suite(150, 7)))
    file_cell = harness.load_cell("fig3.dyskew")
    file_cell["traffic"]["passes"] = 1
    file_cell["config"]["queries"] = {"population": "cust"}
    file_cell["populations"] = str(tmp_path)

    profiles = gen.suite(file_cell["config"]["queries"], str(tmp_path))
    assert profiles == gen.customer_replay_suite(150, 7)
    a, b = harness.generate(suite_cell, seed), harness.generate(file_cell, seed)
    assert [j[0].profile for j in a["pool"]] == [j[0].profile for j in b["pool"]]
    assert [j[0].gap for j in a["pool"]] == [j[0].gap for j in b["pool"]]
    assert a["strategies"] == b["strategies"]
    assert a["warm_strategies"] == b["warm_strategies"]
    assert _traffic_digest(a) == _traffic_digest(b)


def _blob(**over):
    row = {
        "name": "blob", "n_rows": 40, "mean_row_cost": 0.08,
        "cost_sigma": 0.4, "partition_alpha": 0.0, "hot_fraction": 0.0,
        "row_bytes": 1e8, "row_bytes_sigma": 0.3, "batch_rows": 4096,
        "batch_bytes_target": 16e6, "udf": True,
        "locality_constrained": False, "policy": "LATE",
    }
    row.update(over)
    return row


def _skewed(**over):
    return _blob(**{"n_rows": 3000, "mean_row_cost": 2e-3,
                    "cost_sigma": 1.4, "partition_alpha": 0.5,
                    "hot_fraction": 0.05, "row_bytes": 512.0,
                    "batch_rows": 128, **over})


#: About six profiles of the kinds Fig. 5's production population holds:
#: blob rows of 30-300 MB in batches of 4096 rows, a declared Never, a
#: locality-constrained Late, and skewed Late and Eager queries.
TINY_POPULATION = [
    _blob(name="blob_late"),
    _blob(name="blob_eager", n_rows=32, policy="EAGER_SNOWPARK"),
    _skewed(name="bal_never", cost_sigma=0.3, partition_alpha=0.0,
            hot_fraction=0.0, policy="NEVER"),
    _skewed(name="skew_late_local", locality_constrained=True),
    _skewed(name="skew_late"),
    _skewed(name="skew_eager", policy="EAGER_SNOWPARK"),
]

#: Each query under the policy it declares, as `replay.dyskew_strategy`
#: maps it: Never and Early on the row-percentage skew model, and a
#: locality-constrained Eager query under Late.
DECLARED_RULES = [
    {"if": {"policy": "LATE"}, "set": {"dyskew": {"policy": "LATE"}}},
    {"if": {"policy": "NEVER"},
     "set": {"dyskew": {"policy": "NEVER", "skew_model": "ROW_PERCENTAGE"}}},
    {"if": {"policy": "EARLY"},
     "set": {"dyskew": {"policy": "EARLY", "skew_model": "ROW_PERCENTAGE"}}},
    {"if": {"policy": "EAGER_SNOWPARK", "locality_constrained": True},
     "set": {"dyskew": {"policy": "LATE"}}},
]


def _tiny_root(tmp_path, queries, rows=TINY_POPULATION):
    """A checkout holding one cell, ``tiny.declared``: a 2 x 4 warehouse
    whose queries are ``queries``, and the population ``tiny``."""
    root = str(tmp_path)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    config = harness.load_cell("fig3.dyskew")["config"]
    config["warehouse"].update(num_nodes=2, interpreters_per_node=4)
    config["queries"] = queries
    traffic = dict(_traffic("dyskew"), passes=2, compare_queries=12,
                   rules=DECLARED_RULES)
    bench = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.declared", "config": "tiny",
                       "traffic": "declared", "chips": 1}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [],
    }
    for sub, name, doc in (("", "BENCHMARK", bench),
                           ("bench/configs", "tiny", config),
                           ("bench/traffic", "declared", traffic)):
        os.makedirs(os.path.join(root, sub), exist_ok=True)
        with open(os.path.join(root, sub, name + ".json"), "w") as f:
            json.dump(doc, f)
    _write_population(os.path.join(root, "bench", "populations"), "tiny", rows)
    return root


def test_load_cell_finds_a_population_by_name(tmp_path):
    root = _tiny_root(tmp_path, {"population": "tiny"})
    cell = harness.load_cell("tiny.declared", root)
    profiles = gen.suite(cell["config"]["queries"], cell["populations"])
    assert [p.name for p in profiles] == [r["name"] for r in TINY_POPULATION]
    assert [p.policy for p in profiles] == [
        gen.POLICY_IDS[r["policy"]] for r in TINY_POPULATION]
    assert all(isinstance(p.row_bytes, float) for p in profiles)


@pytest.mark.parametrize("queries", [
    {"population": "tiny", "suite": "customer_replay", "num_queries": 5,
     "seed": 7},
    {},
    {"suite": "customer_replay", "num_queries": 5},
    {"population": "tiny", "seed": 7},
], ids=["both", "neither", "suite_short", "population_extra"])
def test_load_cell_refuses_queries_not_of_one_form(queries, tmp_path):
    root = _tiny_root(tmp_path, queries)
    with pytest.raises(SystemExit) as e:
        harness.load_cell("tiny.declared", root)
    assert "queries" in str(e.value)


@pytest.mark.parametrize("field,value,says", [
    ("cost_sigma", None, "'cost_sigma' is missing"),
    ("row_cost", 1e-3, "unknown field 'row_cost'"),
    ("policy", "EAGER", "unknown policy 'EAGER'"),
    ("policy", 3, "unknown policy 3"),
    ("n_rows", 0, "'n_rows' must be positive"),
    ("n_rows", -40, "'n_rows' must be positive"),
    ("batch_rows", 0, "'batch_rows' must be positive"),
    ("n_rows", 40.0, "'n_rows' must be of type int"),
    ("udf", 1, "'udf' must be of type bool"),
])
def test_loader_refuses_a_malformed_profile(field, value, says, tmp_path):
    rows = copy.deepcopy(TINY_POPULATION)
    if value is None:
        del rows[3][field]
    else:
        rows[3][field] = value
    _write_population(str(tmp_path), "bad", rows)
    with pytest.raises(ValueError) as e:
        gen.load_population("bad", str(tmp_path))
    assert "profile 3 ('skew_late_local')" in str(e.value)
    assert says in str(e.value)


def test_loader_refuses_an_empty_population(tmp_path):
    _write_population(str(tmp_path), "empty", [])
    with pytest.raises(ValueError):
        gen.load_population("empty", str(tmp_path))


@pytest.mark.parametrize("name", sorted(gen.POLICY_IDS))
def test_rule_naming_a_policy_resolves_as_its_id(name):
    base = _traffic("dyskew")
    by_name = dict(base, rules=[{"if": {"policy": name},
                                 "set": {"dyskew": {"theta": 0.25}}}])
    by_id = dict(base, rules=[{"if": {"policy": gen.POLICY_IDS[name]},
                               "set": {"dyskew": {"theta": 0.25}}}])
    hits = 0
    for pid in gen.POLICY_IDS.values():
        for constrained in (False, True):
            prof = gen.QueryProfile("q", policy=pid,
                                    locality_constrained=constrained)
            got = strategy.resolve(by_name, prof)
            assert got == strategy.resolve(by_id, prof)
            hits += got["dyskew"]["theta"] == 0.25
    assert hits == 2


def test_rule_naming_an_unknown_policy_is_refused():
    traffic = dict(_traffic("dyskew"), rules=[
        {"if": {"policy": "EAGER"}, "set": {"dyskew": {"theta": 0.25}}}])
    with pytest.raises(ValueError, match="unknown policy 'EAGER'"):
        strategy.resolve(traffic, gen.QueryProfile("q"))


def test_declared_rules_match_replay_dyskew_strategy():
    """The rules the end-to-end test below runs under give every query of
    Fig. 5's production population the strategy `replay.dyskew_strategy`
    gives it."""
    from repro.sim.replay import dyskew_strategy
    from repro.sim.workload import production_mix

    traffic = dict(_traffic("dyskew"), rules=DECLARED_RULES)
    origs = production_mix(200, 23)
    assert {o.policy for o in origs} == {0, 1, 3}
    for o in origs:
        prof = gen.QueryProfile(**{**dataclasses.asdict(o),
                                   "policy": int(o.policy)})
        got = program_io.strategy(strategy.resolve(traffic, prof))
        assert got == dyskew_strategy(o)


def test_population_cell_runs_correct_end_to_end(tmp_path):
    """A population-file configuration through the whole of a run, the
    look for a chip skipped: blob rows, Never, Late and Eager each reach
    the program and the reference, and the comparison holds."""
    import jax

    seen = {}

    class Recording(program_io.Program):
        def run(self, entry):
            out = super().run(entry)
            for r, t in zip(out[0], self.jobs[entry]):
                seen[t.name.split("#")[0]] = r
            return out

    root = _tiny_root(tmp_path, {"population": "tiny"})
    cell = harness.load_cell("tiny.declared", root)
    out = harness.run(cell, 2**32 + 9, 0.05, False, jax.devices(),
                      time.perf_counter(), log=lambda m: None,
                      program_cls=Recording)
    assert out["correct"] and out["failed"] == 0
    assert out["checks"]["result_rel_gap"]["value"] == 0.0
    assert out["checks"]["link_state_mismatch"]["value"] == 0.0
    assert set(seen) == {r["name"] for r in TINY_POPULATION}
    # Never moves no row; skewed Eager does.
    assert seen["bal_never"].rows_redistributed == 0
    assert seen["skew_eager"].rows_redistributed > 0
    # Every blob row is heavy to the Row Size Model.
    heavy = cell["traffic"]["strategy"]["dyskew"]["heavy_row_bytes"]
    blobs = [q for q in gen.query_pool(
        gen.suite(cell["config"]["queries"], cell["populations"]), 8, 2.0, 3)
        if q.profile.name.startswith("blob")]
    assert min(float(s.min()) for q in blobs for st in q.streams
               for _, s in st) >= heavy


def test_population_cell_control_is_refused(tmp_path):
    """The float32 reference in the program's place fails the same run."""
    import jax

    from bench.control import ReferenceProgram

    root = _tiny_root(tmp_path, {"population": "tiny"})
    cell = harness.load_cell("tiny.declared", root)
    out = harness.run(cell, 2**32 + 9, 0.05, False, jax.devices(),
                      time.perf_counter(), log=lambda m: None,
                      program_cls=ReferenceProgram)
    assert not out["correct"]
