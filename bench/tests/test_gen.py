"""The benchmark's copies of the traffic generators and strategy rules
give, for a seed, exactly what the program's originals give."""

import dataclasses
import json
import os

import numpy as np
import pytest

from bench import gen, program_io, strategy

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
