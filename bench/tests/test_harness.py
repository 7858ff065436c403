"""The harness's window arithmetic, its look-up of a cell's files by
name, the per-layer readers, and its refusal to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_window_runs_past_its_length_and_rates_over_all_of_it():
    clock = FakeClock()
    durations = [0.3, 0.3, 0.3, 3.0, 0.3]

    def job(i):
        clock.t += durations[i]
        return i

    win = harness.run_window(job, 1.0, clock=clock)
    # The job running at 1.0 s (the fourth) ends the window at 3.9 s.
    assert win["outs"] == [0, 1, 2, 3]
    assert win["walls"] == pytest.approx([0.3, 0.3, 0.3, 3.0])
    assert win["end"] - win["start"] == pytest.approx(3.9)
    assert harness.rate(78.0, win) == pytest.approx(20.0)


def test_window_holds_at_least_one_job():
    clock = FakeClock()

    def job(i):
        clock.t += 5.0
        return i

    win = harness.run_window(job, 1.0, clock=clock)
    assert win["outs"] == [0]


def test_p95_counts_its_samples_and_those_beyond():
    q = harness.p95([float(i) for i in range(1, 201)])
    assert q["value"] == pytest.approx(190.05)
    assert q["n"] == 200 and q["beyond"] == 10


def test_derived_seeds_take_large_seeds_and_differ_by_pass():
    big = 2**31 + 12345
    a, b = harness.derived_seed(big, 3, 0), harness.derived_seed(big, 3, 1)
    assert a != b and a == harness.derived_seed(big, 3, 0)


def _small_fig3():
    cell = harness.load_cell("fig3.dyskew")
    cell["config"]["warehouse"].update(num_nodes=2, interpreters_per_node=4)
    cell["config"]["queries"]["num_queries"] = 12
    cell["traffic"]["passes"] = 3
    return cell


def test_each_pass_draws_every_query_anew():
    cell = _small_fig3()
    g = harness.generate(cell, 2**33 + 7)
    pool = g["pool"]
    assert len(pool) == 3 * 12 and all(len(job) == 1 for job in pool)
    passes = [[job[0] for job in pool[p * 12:(p + 1) * 12]] for p in range(3)]
    names = [sorted(q.profile.name for q in qs) for qs in passes]
    # Every pass holds every query once, so every pass has the same work.
    assert names[0] == names[1] == names[2] and len(set(names[0])) == 12
    assert len({sum(q.rows for q in qs) for qs in passes}) == 1
    # Each pass in its own order, with rows of its own.
    assert [q.profile.name for q in passes[0]] != [q.profile.name for q in passes[1]]
    costs = [{q.profile.name: q.cost for q in qs} for qs in passes]
    assert all(costs[0][k] != costs[1][k] != costs[2][k] for k in costs[0])


def test_warm_up_has_one_query_of_its_own_per_strategy():
    cell = _small_fig3()
    cell["config"]["queries"]["num_queries"] = 150
    cell["traffic"]["passes"] = 1
    g = harness.generate(cell, 5)
    kinds = {json.dumps(s[0], sort_keys=True) for s in g["strategies"]}
    assert len(g["warm"]) == len(kinds) == 2
    assert {json.dumps(s[0], sort_keys=True) for s in g["warm_strategies"]} == kinds
    window_costs = {job[0].cost for job in g["pool"]}
    assert not any(job[0].cost in window_costs for job in g["warm"])


def test_the_same_seed_gives_the_same_traffic():
    cell = _small_fig3()
    a, b = harness.generate(cell, 2**31 + 12345), harness.generate(cell, 2**31 + 12345)
    c = harness.generate(cell, 2**31 + 12346)
    assert [j[0].cost for j in a["pool"]] == [j[0].cost for j in b["pool"]]
    assert [j[0].cost for j in a["pool"]] != [j[0].cost for j in c["pool"]]


def test_program_gets_fresh_input_objects_for_every_job():
    from bench.program_io import Program

    cell = _small_fig3()
    g = harness.generate(cell, 11)
    prog = Program(cell["config"]["warehouse"], g["pool"], g["strategies"])
    tenants = [t for job in prog.jobs for t in job]
    assert len({id(t) for t in tenants}) == len(tenants) == len(g["pool"])
    batches = [id(b) for t in tenants for stream in t.streams for b in stream]
    assert len(set(batches)) == len(batches)


def test_link_rows_refuses_states_it_cannot_map():
    import numpy as np

    from bench import program_io

    cell = _small_fig3()
    st = harness.strategy.resolve(cell["traffic"], harness.gen.suite(
        cell["config"]["queries"])[0])
    lone = {"state": np.zeros(8, np.int32)}
    with pytest.raises(ValueError):
        program_io.link_rows([lone], [st])
    stacked = {"state": np.zeros((1, 8), np.int32)}
    assert program_io.link_rows([stacked], [st])[0]["state"].shape == (8,)


def test_every_cell_finds_its_files_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["traffic"]["job"] in ("query", "concurrent")
        assert set(cell["config"]["correct_limits"]) == set(harness.compare.NUMBERS)
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        for m in cell["per_layer"]:
            assert os.path.isfile(os.path.join(ROOT, "bench", "metrics", m["name"] + ".py"))


@pytest.mark.parametrize("metric", [
    "compiles_in_window", "heap_events_per_krow", "tick_calls_per_krow",
    "device_busy_us_per_call", "device_idle_share"])
def test_readers_return_nothing_when_there_is_nothing_to_read(metric):
    read = harness._load_reader(metric)
    assert read({}) is None
    obs = {
        "compiles_in_window": 0,
        "traced": {"rows": 2000, "counts": {"heap_events": 600, "tick": 1, "gtick": 3}},
        "trace": {"busy_s": 0.002, "window_s": 0.5},
    }
    want = {
        "compiles_in_window": 0,
        "heap_events_per_krow": 300.0,
        "tick_calls_per_krow": 2.0,
        "device_busy_us_per_call": 500.0,
        "device_idle_share": 99.6,
    }[metric]
    assert read(obs) == pytest.approx(want)


def test_no_tick_reads_nothing_per_call():
    obs = {"traced": {"rows": 10, "counts": {"heap_events": 5}},
           "trace": {"busy_s": 0.001, "window_s": 0.5}}
    assert harness._load_reader("tick_calls_per_krow")(obs) is None
    assert harness._load_reader("device_busy_us_per_call")(obs) is None


def test_require_chips_refuses_the_cpu():
    with pytest.raises(SystemExit) as e:
        harness.require_chips(1)
    assert "TPU" in str(e.value)


def test_run_cell_exits_nonzero_and_prints_nothing_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join("bench", "run_cell.py"), "--workload",
         "fig3.dyskew", "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr
