"""Jobs of several concurrent queries (``"job": "concurrent"``): their
queries are those a ``"query"`` traffic draws, cut into jobs; their
arrivals come from the program's own arrival processes, snapped onto the
tick grid the program batches; a malformed traffic file is refused; the
warm-up covers every tick group a job can form; and such a cell runs
through the whole of a run, correct, with grouped ticks alone and no
compilation in its window."""

import copy
import json
import os
import time
import types

import jax
import numpy as np
import pytest

from bench import gen, harness, program_io
from bench.tests.test_gen import _traffic_digest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
STEP = 0.05
POISSON = {"process": "poisson", "rate": 4.0}
BURST = {"process": "burst", "rate": 2.0, "burst_factor": 8.0,
         "burst_fraction": 0.25, "mean_burst_s": 2.0}


def _traffic(name="dyskew"):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _concurrent(traffic, k=8, arrivals=POISSON):
    out = {key: v for key, v in traffic.items() if key != "job"}
    return dict(out, job="concurrent", concurrency=k,
                arrivals=copy.deepcopy(arrivals))


def _fig3(passes=2, **kw):
    """`fig3.dyskew`'s configuration under a concurrent traffic."""
    cell = harness.load_cell("fig3.dyskew")
    cell["traffic"] = _concurrent(cell["traffic"], **kw)
    cell["traffic"]["passes"] = passes
    return cell


# --------------------------------------------------------------------- #
# Generation
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("seed", [2_147_483_659, 2**32 + 17])
def test_the_same_seed_gives_bit_identical_concurrent_traffic(seed):
    cell = _fig3(passes=1)
    a, b = harness.generate(cell, seed), harness.generate(cell, seed)
    assert _traffic_digest(a) == _traffic_digest(b)
    assert _traffic_digest(a) != _traffic_digest(harness.generate(cell, seed + 1))
    assert any(q.arrival > 0 for job in a["pool"] for q in job)


@pytest.mark.parametrize("k", [1, 3, 8])
def test_jobs_hold_the_query_traffic_cut_into_k(k):
    seed = 2**32 + 17
    solo = harness.load_cell("fig3.dyskew")
    solo["traffic"]["passes"] = 2
    cell = _fig3(passes=2, k=k)
    a, b = harness.generate(solo, seed), harness.generate(cell, seed)
    queries = [q for job in b["pool"] for q in job]
    # 300 queries: jobs of k, and the remainder last.
    assert [len(job) for job in b["pool"]] == (
        [k] * (300 // k) + ([300 % k] if 300 % k else []))
    assert len(queries) == len(a["pool"]) == 300
    assert all(_same_query(q, c) for (q,), c in zip(a["pool"], queries))
    assert [s for job in a["strategies"] for s in job] == [
        s for job in b["strategies"] for s in job]
    assert all(job[0].arrival == 0.0 for job in b["pool"])
    assert all(q.arrival == 0.0 for (q,) in a["pool"])


def _same_query(q, c):
    return (q.profile == c.profile and q.gap == c.gap
            and len(q.streams) == len(c.streams)
            and all(len(x) == len(y) and all(
                np.array_equal(xc, yc) and np.array_equal(xs, ys)
                for (xc, xs), (yc, ys) in zip(x, y))
                for x, y in zip(q.streams, c.streams)))


@pytest.mark.parametrize("arrivals", [POISSON, BURST], ids=["poisson", "burst"])
@pytest.mark.parametrize("seed", [11, 2**32 + 17])
def test_arrival_process_is_the_programs(arrivals, seed):
    from repro.sim.workload import ArrivalProcess, arrival_times

    proc = ArrivalProcess(kind=arrivals["process"],
                          **{k: v for k, v in arrivals.items() if k != "process"})
    want = arrival_times(proc, 500, seed)
    got = gen.arrival_times(arrivals, 500, seed)
    assert np.array_equal(got, want)


def test_every_arrival_is_on_the_chained_grid_of_the_program():
    """Each arrival equals, bit for bit, what `replay.open_loop_tenants`'s
    chain gives the same time, and every tick group of every job passes
    the engine's own grid test, so the program batches it."""
    from repro.sim import replay
    from repro.sim.engine import ClusterConfig, _arrivals_on_grid
    from repro.sim.workload import ArrivalProcess, QueryProfile

    cell = _fig3(passes=2, k=8, arrivals=BURST)
    g = harness.generate(cell, 2**32 + 5)
    checked = 0
    for j, (job, strats) in enumerate(zip(g["pool"], g["strategies"])):
        raw = gen.arrival_times(BURST, len(job), harness.derived_seed(
            2**32 + 5, 6, j))
        raw = raw - raw[0]
        replay_arrivals = [t.arrival for t in _replay_chain(
            replay, ClusterConfig(num_nodes=1, interpreters_per_node=2),
            ArrivalProcess(), QueryProfile("q", n_rows=4), raw)]
        assert [q.arrival for q in job] == replay_arrivals
        assert _arrivals_on_grid([0.0] + replay_arrivals, STEP)
        groups = {}
        for q, st in zip(job, strats):
            groups.setdefault(json.dumps(st, sort_keys=True), []).append(
                q.arrival)
        for times in groups.values():
            assert _arrivals_on_grid(times, STEP)
            checked += 1
    assert checked > len(g["pool"])
    assert len({q.arrival for job in g["pool"] for q in job}) > 50


def _replay_chain(replay, cluster, process, prof, raw):
    orig = replay.arrival_times
    replay.arrival_times = lambda *a, **k: raw
    try:
        return replay.open_loop_tenants(
            [(prof, 1.0)], cluster, lambda p: None, process, len(raw),
            seed=3, grid_align=STEP)
    finally:
        replay.arrival_times = orig


def test_poisson_gaps_average_one_over_the_rate():
    gaps = []
    for j in range(2000):
        t = gen.job_arrivals(POISSON, 8, STEP, harness.derived_seed(5, 6, j))
        assert t[0] == 0.0 and t == sorted(t)
        gaps += list(np.diff(t))
    assert len(gaps) == 14_000
    assert np.mean(gaps) == pytest.approx(1.0 / POISSON["rate"], rel=0.05)


def test_burst_process_spends_its_fraction_of_time_in_bursts():
    """The mean rate of the on/off process is ``rate * (1 - f + f *
    burst_factor)``; the share of time in bursts ``f`` read back from it
    is the one the traffic states."""
    times = gen.arrival_times(BURST, 200_000, 2**32 + 3)
    mean_rate = len(times) / times[-1]
    share = (mean_rate / BURST["rate"] - 1.0) / (BURST["burst_factor"] - 1.0)
    assert share == pytest.approx(BURST["burst_fraction"], abs=0.02)
    # Bursty: the gaps spread more than a Poisson stream's, whose
    # standard deviation equals their mean.
    gaps = np.diff(times)
    assert np.std(gaps) > 1.5 * np.mean(gaps)


def test_on_grid_refuses_a_span_beyond_the_programs_walk():
    with pytest.raises(ValueError, match="ticks"):
        gen.on_grid(np.asarray([0.0, STEP * (gen.MAX_GRID_STEPS + 3)]), STEP)


def test_warm_up_holds_one_job_per_strategy_and_size():
    cell = _fig3(passes=1, k=4)
    g = harness.generate(cell, 2**31 + 99)
    profiles = gen.suite(cell["config"]["queries"])
    kinds = []
    for prof in profiles:
        key = json.dumps(harness.strategy.resolve(cell["traffic"], prof),
                         sort_keys=True)
        if key not in kinds:
            kinds.append(key)
    assert len(kinds) == 2
    assert len(g["warm"]) == 2 * 4
    assert [len(job) for job in g["warm"]] == [1, 2, 3, 4] * 2
    for w, (job, strats) in enumerate(zip(g["warm"], g["warm_strategies"])):
        assert {json.dumps(s, sort_keys=True) for s in strats} == {kinds[w // 4]}
        assert all(q.arrival == 0.0 for q in job)
        # The strategy's query with the fewest rows.
        fewest = min(p.n_rows for p in profiles
                     if json.dumps(harness.strategy.resolve(
                         cell["traffic"], p), sort_keys=True) == kinds[w // 4])
        assert all(q.rows == fewest for q in job)
        assert len({q.cost for q in job}) == len(job)
    window = {q.cost for job in g["pool"] for q in job}
    assert not any(q.cost in window for job in g["warm"] for q in job)


# --------------------------------------------------------------------- #
# Refusals
# --------------------------------------------------------------------- #


def _root_with(tmp_path, traffic):
    """A checkout whose one cell ``fig3.c`` runs `large_wh_fig3` under
    ``traffic``."""
    root = str(tmp_path)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": "fig3.c", "config": "large_wh_fig3",
                           "traffic": "c", "chips": 1}]
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(ROOT, "bench", "configs", "large_wh_fig3.json")) as f:
        config = f.read()
    with open(os.path.join(root, "bench", "configs", "large_wh_fig3.json"),
              "w") as f:
        f.write(config)
    with open(os.path.join(root, "bench", "traffic", "c.json"), "w") as f:
        json.dump(traffic, f)
    return root


def _drop(d, key):
    d.pop(key)


BAD = {
    "concurrency_missing": (lambda t: _drop(t, "concurrency"), "'concurrency' is missing"),
    "concurrency_zero": (lambda t: t.update(concurrency=0), "'concurrency' must be a positive int"),
    "concurrency_negative": (lambda t: t.update(concurrency=-2), "'concurrency' must be a positive int"),
    "concurrency_float": (lambda t: t.update(concurrency=8.0), "'concurrency' must be a positive int"),
    "arrivals_missing": (lambda t: _drop(t, "arrivals"), "'arrivals' is missing"),
    "process_missing": (lambda t: _drop(t["arrivals"], "process"), "'arrivals.process' is missing"),
    "process_unknown": (lambda t: t["arrivals"].update(process="gamma"), "unknown process 'gamma'"),
    "rate_missing": (lambda t: _drop(t["arrivals"], "rate"), "'arrivals.rate' is missing"),
    "rate_zero": (lambda t: t["arrivals"].update(rate=0.0), "'arrivals.rate' must be a positive number"),
    "rate_negative": (lambda t: t["arrivals"].update(rate=-1.0), "'arrivals.rate' must be a positive number"),
    "rate_string": (lambda t: t["arrivals"].update(rate="4"), "'arrivals.rate' must be a positive number"),
    "field_unknown": (lambda t: t["arrivals"].update(lam=4.0), "'arrivals.lam' is unknown"),
    "burst_field_on_poisson": (lambda t: t["arrivals"].update(burst_factor=8.0), "'arrivals.burst_factor' is unknown"),
    "burst_factor_missing": (lambda t: t.update(arrivals={k: v for k, v in BURST.items() if k != "burst_factor"}), "'arrivals.burst_factor' is missing"),
    "burst_fraction_missing": (lambda t: t.update(arrivals={k: v for k, v in BURST.items() if k != "burst_fraction"}), "'arrivals.burst_fraction' is missing"),
    "mean_burst_s_missing": (lambda t: t.update(arrivals={k: v for k, v in BURST.items() if k != "mean_burst_s"}), "'arrivals.mean_burst_s' is missing"),
    "burst_factor_zero": (lambda t: t.update(arrivals=dict(BURST, burst_factor=0)), "'arrivals.burst_factor' must be a positive number"),
    "burst_fraction_zero": (lambda t: t.update(arrivals=dict(BURST, burst_fraction=0.0)), "'arrivals.burst_fraction' must be a positive number"),
    "burst_fraction_one": (lambda t: t.update(arrivals=dict(BURST, burst_fraction=1.0)), "'arrivals.burst_fraction' must lie below 1"),
    "mean_burst_s_negative": (lambda t: t.update(arrivals=dict(BURST, mean_burst_s=-2.0)), "'arrivals.mean_burst_s' must be a positive number"),
    "tick_intervals_differ": (lambda t: t["rules"].append({"if": {"locality_constrained": True}, "set": {"tick_interval": 0.1}}), "'tick_interval': the strategies of a concurrent job must share one"),
    "job_unknown": (lambda t: t.update(job="batch"), "'job': unknown job kind 'batch'"),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_load_cell_refuses_a_malformed_concurrent_traffic(case, tmp_path):
    traffic = _concurrent(_traffic())
    spoil, says = BAD[case]
    spoil(traffic)
    root = _root_with(tmp_path, traffic)
    with pytest.raises(SystemExit) as e:
        harness.load_cell("fig3.c", root)
    assert "bench/traffic/c.json" in str(e.value)
    assert says in str(e.value)


@pytest.mark.parametrize("arrivals", [POISSON, BURST], ids=["poisson", "burst"])
def test_load_cell_takes_a_well_formed_concurrent_traffic(arrivals, tmp_path):
    root = _root_with(tmp_path, _concurrent(_traffic(), arrivals=arrivals))
    cell = harness.load_cell("fig3.c", root)
    assert cell["traffic"]["job"] == "concurrent"


@pytest.mark.parametrize("field", ["concurrency", "arrivals"])
def test_load_cell_refuses_concurrent_fields_on_a_query_traffic(field, tmp_path):
    traffic = dict(_traffic(), **{field: _concurrent({})[field]})
    root = _root_with(tmp_path, traffic)
    with pytest.raises(SystemExit) as e:
        harness.load_cell("fig3.c", root)
    assert f"field {field!r} is for a concurrent job" in str(e.value)


# --------------------------------------------------------------------- #
# The sample the check compares
# --------------------------------------------------------------------- #


def _pool(*jobs):
    return [[types.SimpleNamespace(rows=r) for r in job] for job in jobs]


def test_longest_job_is_the_one_with_most_rows_in_all_its_queries():
    # Job 2's first query is the shortest of all, but it holds the most rows.
    pool = _pool([9_000, 9_000], [20_000], [100, 15_000, 15_000], [5_000])
    for seed in range(20):
        assert 2 in harness.sample_jobs(pool, [0, 1, 2, 3], 1, seed)
    assert harness.sample_jobs(pool, [0, 1, 3], 1, 7) == {1}


@pytest.mark.parametrize("seed", [0, 4, 2**32 + 17])
def test_one_query_jobs_sample_as_before(seed):
    pool = _pool(*[[int(r)] for r in
                   np.random.default_rng(seed).integers(6_000, 30_000, 60)])
    ran = sorted(np.random.default_rng(seed + 1).choice(60, 40, replace=False))
    ran = [int(e) for e in ran]
    longest = max(ran, key=lambda e: pool[e][0].rows)
    rest = [e for e in ran if e != longest]
    pick = np.random.default_rng([seed, 4]).choice(len(rest), 15, replace=False)
    want = {longest, *(rest[int(i)] for i in pick)}
    assert harness.sample_jobs(pool, ran, 16, seed) == want


@pytest.mark.parametrize("seed", [1, 2**32 + 3])
def test_concurrent_sample_takes_whole_jobs_until_enough_queries(seed):
    pool = _pool(*[[1_000] * 8] * 30, [1_000] * 3)
    ran = list(range(31))
    for want in (1, 8, 9, 16, 17, 40):
        got = harness.sample_jobs(pool, ran, want, seed)
        assert 0 in got
        held = sum(len(pool[e]) for e in got)
        # At least ``want`` queries, and no job more than that takes.
        assert held >= want
        assert held - max((len(pool[e]) for e in got - {0}), default=8) < want


# --------------------------------------------------------------------- #
# A concurrent cell through the whole of a run
# --------------------------------------------------------------------- #


def _profile(name, **over):
    row = {"name": name, "n_rows": 2_400, "mean_row_cost": 2e-3,
           "cost_sigma": 1.2, "partition_alpha": 0.4, "hot_fraction": 0.04,
           "row_bytes": 512.0, "row_bytes_sigma": 0.3, "batch_rows": 128,
           "batch_bytes_target": 16e6, "udf": True,
           "locality_constrained": False, "policy": "EAGER_SNOWPARK"}
    row.update(over)
    return row


#: Skewed and balanced queries under Eager, and locality-constrained
#: ones that the traffic's rule puts under Late.
TINY = [
    _profile("skew_a"),
    _profile("skew_b", n_rows=1_800, hot_fraction=0.08),
    _profile("bal", cost_sigma=0.3, partition_alpha=0.0, hot_fraction=0.0),
    _profile("local_a", locality_constrained=True),
    _profile("local_b", n_rows=1_500, locality_constrained=True),
    _profile("heavy_cost", mean_row_cost=4e-3, n_rows=1_200),
]


def _tiny_root(tmp_path):
    """A checkout whose one cell, ``tiny.concurrent``, runs a 2 x 4
    warehouse under jobs of 4 concurrent queries with Poisson arrivals."""
    root = str(tmp_path)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    config = harness.load_cell("fig3.dyskew")["config"]
    config["warehouse"].update(num_nodes=2, interpreters_per_node=4)
    config["queries"] = {"population": "tiny"}
    traffic = dict(_concurrent(_traffic(), k=4,
                               arrivals={"process": "poisson", "rate": 3.0}),
                   passes=2, compare_queries=12)
    bench = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.concurrent", "config": "tiny",
                       "traffic": "concurrent", "chips": 1}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [],
    }
    for sub, name, doc in (("", "BENCHMARK", bench),
                           ("bench/configs", "tiny", config),
                           ("bench/traffic", "concurrent", traffic),
                           ("bench/populations", "tiny",
                            {"about": "test", "source": "test",
                             "profiles": TINY})):
        os.makedirs(os.path.join(root, sub), exist_ok=True)
        with open(os.path.join(root, sub, name + ".json"), "w") as f:
            json.dump(doc, f)
    return root


class _Compiles:
    def __init__(self):
        self.count = 0

    def __call__(self, event, secs, **_):
        if event == harness.COMPILE_EVENT:
            self.count += 1


def test_concurrent_cell_runs_correct_end_to_end(tmp_path):
    """Through the whole of a run, the look for a chip skipped: every
    window job ticks its links in batched groups alone (the harness's
    grid agrees with the engine's), compiles nothing, and matches the
    reference with every gap 0."""
    compiles = _Compiles()
    calls = []

    class Recording(program_io.Program):
        def __init__(self, *a):
            super().__init__(*a)
            self.window = not calls
            calls.append([])

        def run(self, entry):
            before = compiles.count
            out = super().run(entry)
            calls[-1 if not self.window else 0].append({
                "counts": out[1], "compiles": compiles.count - before,
                "arrivals": [t.arrival for t in self.jobs[entry]],
                "kinds": {t.strategy.dyskew.policy for t in self.jobs[entry]}})
            return out

    root = _tiny_root(tmp_path)
    cell = harness.load_cell("tiny.concurrent", root)
    # Compiled programs of earlier tests would hide a shape the warm-up
    # missed.
    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        out = harness.run(cell, 2**32 + 9, 0.3, False, jax.devices(),
                          time.perf_counter(), log=lambda m: None,
                          program_cls=Recording)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    assert out["correct"] and out["failed"] == 0
    assert out["checks"]["result_rel_gap"]["value"] == 0.0
    assert out["checks"]["link_state_mismatch"]["value"] == 0.0
    window, warm = calls
    for c in window:
        assert c["compiles"] == 0
        assert c["counts"]["gtick"] > 0 and c["counts"]["tick"] == 0
    assert len(warm) == 2 * 4 and sum(c["compiles"] for c in warm) > 0
    assert out["attempted"] == sum(len(c["arrivals"]) for c in window)
    # The first job mixes both strategies and staggers its arrivals.
    assert len(window[0]["kinds"]) == 2
    assert len(set(window[0]["arrivals"])) == 4


def test_concurrent_cell_control_is_refused(tmp_path):
    """The float32 reference in the program's place fails the same run."""
    from bench.control import ReferenceProgram

    cell = harness.load_cell("tiny.concurrent", _tiny_root(tmp_path))
    out = harness.run(cell, 2**32 + 9, 0.3, False, jax.devices(),
                      time.perf_counter(), log=lambda m: None,
                      program_cls=ReferenceProgram)
    assert not out["correct"]
