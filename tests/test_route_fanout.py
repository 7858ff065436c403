"""`route_batch`'s per-destination fan-out, routed as arrays.

A redistributed batch is grouped by destination and every group's
bytes, uplink finish time and arrival are computed for all groups at
once; one loop is left, which pushes the _ENQUEUE events in ascending
destination order.  The trajectory must stay bit-identical to routing
one group at a time, so this suite pins:

  * the per-group byte helper against numpy's own ``x[lo:hi].sum()``,
    bit for bit, over every group length class (the helper's
    left-to-right columns below 8 rows reproduce numpy's summation
    order there; from 8 rows up it calls ``.sum()`` itself);
  * digests of the routing branches the benchmark's Fig. 3 cell never
    takes (no NIC contention, a degraded NIC, the autoscale and fault
    redirects of a decommissioned producer, per-row policies, coalesced
    same-instant runs), taken from the one-group-at-a-time loop;
  * the ``route_groups`` / ``route_groups_summed`` counters.
"""

import hashlib

import numpy as np
import pytest

from repro.core.admission import AutoscaleConfig
from repro.core.policy import StrategyConfig
from repro.runtime.fault_tolerance import FaultConfig
from repro.sim import engine
from repro.sim.engine import ClusterConfig, MultiQuerySimulator, TenantQuery
from repro.sim.faults import NIC_DEGRADE, PREEMPT, FaultEvent, FaultSchedule
from repro.sim.spans import Spans
from repro.sim.workload import QueryProfile, generate_query


# ------------------------------------------------------------------ #
# Per-group bytes
# ------------------------------------------------------------------ #

def _bounds(cuts, n):
    starts = np.concatenate(([0], cuts)).astype(np.int64)
    ends = np.concatenate((cuts, [n])).astype(np.int64)
    return starts, ends


def _check_group_bytes(x, starts, ends):
    got, n_summed = engine._group_bytes(x, starts, ends)
    want = [float(x[lo:hi].sum()) for lo, hi in zip(starts, ends)]
    # Bit equality, not closeness: the digests depend on it.
    assert got.dtype == np.float64
    assert got.tobytes() == np.asarray(want, np.float64).tobytes()
    assert n_summed == int(((ends - starts) >= 8).sum())


class TestGroupBytes:
    @pytest.mark.parametrize("seed", range(6))
    def test_random_cuts_match_numpy_sum(self, seed):
        """Random cuts of lognormal sizes: lengths 1-7 (the columns),
        8-127 and 128 and up (numpy's pairwise blocks) all occur."""
        rng = np.random.default_rng(seed)
        seen = set()
        for trial in range(200):
            n = int(rng.integers(1, 301))
            x = rng.lognormal(6.0, 1.5, n)
            # Many cuts (short groups) or a few (long groups).
            k = int(rng.integers(0, n if trial % 2 else 3))
            cuts = np.unique(rng.integers(1, n, k)) if n > 1 else []
            starts, ends = _bounds(np.asarray(cuts, np.int64), n)
            _check_group_bytes(x, starts, ends)
            lens = ends - starts
            seen.update({"short" if m < 8 else "mid" if m < 128 else "long"
                         for m in lens.tolist()})
        assert seen == {"short", "mid", "long"}

    @pytest.mark.parametrize("length", [1, 2, 7, 8, 9, 15, 16, 127, 128,
                                        129, 300])
    def test_one_group_of_every_row(self, length):
        """Every row of the batch going to one destination."""
        x = np.random.default_rng(length).lognormal(6.0, 1.5, length)
        starts, ends = _bounds(np.zeros(0, np.int64), length)
        _check_group_bytes(x, starts, ends)

    def test_one_row_batch(self):
        x = np.array([512.25])
        _check_group_bytes(x, *_bounds(np.zeros(0, np.int64), 1))

    def test_signed_zeros(self):
        """numpy's sum starts from 0.0: a group of -0.0 sums to 0.0."""
        x = np.array([-0.0, -0.0, -0.0, 2.5, -0.0, 0.0, -0.0, -1.5,
                      -0.0, -0.0, 3.0])
        _check_group_bytes(x, *_bounds(np.array([1, 3, 5, 7, 8]), 11))
        _check_group_bytes(x, *_bounds(np.array([2, 4, 9]), 11))

    def test_one_row_groups(self):
        x = np.random.default_rng(1).lognormal(6.0, 1.5, 64)
        _check_group_bytes(x, *_bounds(np.arange(1, 64), 64))


# ------------------------------------------------------------------ #
# Branch digests
# ------------------------------------------------------------------ #

FCFG = FaultConfig(heartbeat_interval=0.02, missed_beats_dead=2,
                   n_strikes=3, slope_window=8, min_hosts=2)
SMALL = ClusterConfig(num_nodes=2, interpreters_per_node=4)


def _tenant(kind, cluster, seed, n_rows=4096, alpha=1.4, arrival=0.0,
            name="t"):
    prof = QueryProfile(
        name=name, n_rows=n_rows, partition_alpha=alpha,
        hot_fraction=0.3, cost_sigma=0.8,
    )
    return TenantQuery(
        name=name,
        streams=generate_query(prof, n_producers=cluster.num_workers,
                               seed=seed),
        strategy=StrategyConfig(kind=kind), arrival=arrival,
    )


def _case(name):
    """(cluster, simulator kwargs, tenants) of one pinned branch."""
    c = SMALL
    if name == "no_contention":
        c = ClusterConfig(num_nodes=2, interpreters_per_node=4,
                          model_contention=False)
        return c, {}, [_tenant("dyskew", c, 11)]
    if name in ("nic_degrade", "nic_degrade_no_contention"):
        c = ClusterConfig(num_nodes=2, interpreters_per_node=4,
                          model_contention=name == "nic_degrade")
        sched = FaultSchedule(events=(
            FaultEvent(time=0.0, kind=NIC_DEGRADE, worker=0, factor=3.0,
                       duration=10.0),
        ))
        return c, {"faults": sched, "fault_cfg": FCFG}, \
            [_tenant("dyskew", c, 11)]
    if name == "faults_redirect":
        # Preemption notices flip the producers unroutable at once: the
        # 'none' policy keeps their rows local, so they redirect.
        sched = FaultSchedule(events=tuple(
            FaultEvent(time=0.01, kind=PREEMPT, worker=w, notice=0.005,
                       duration=0.3)
            for w in (0, 5)
        ))
        return c, {"faults": sched, "fault_cfg": FCFG}, \
            [_tenant("none", c, 7)]
    if name == "autoscale_redirect":
        # The pool starts at min_workers: the other producers are
        # decommissioned, so the 'none' policy's rows redirect.
        asc = AutoscaleConfig(min_workers=2, max_workers=c.num_workers,
                              backlog_high=16.0, backlog_low=4.0, step=2,
                              interval=0.02, cooldown=0.0)
        return c, {"autoscale": asc}, [_tenant("none", c, 7)]
    if name in ("static_rr", "p2c", "key_affinity", "hillclimb"):
        return c, {"seed": 3}, [_tenant(name, c, 13)]
    if name in ("static_rr_coalesced", "dyskew_coalesced"):
        kind = name[:-len("_coalesced")]
        return c, {}, [_tenant(kind, c, 20 + i, n_rows=2048, name=f"t{i}")
                       for i in range(3)]
    if name == "dyskew_large_wh":
        c = ClusterConfig(num_nodes=8, interpreters_per_node=8)
        return c, {}, [_tenant("dyskew", c, 5, n_rows=6000, alpha=1.1)]
    raise KeyError(name)


def _run_case(name, pushes=None):
    """Run one case; with a list for ``pushes``, every heap event the
    run pushes is appended to it, in push order."""
    cluster, kw, tenants = _case(name)
    sim = MultiQuerySimulator(cluster, **kw)
    if pushes is None:
        return sim, sim.run(tenants)
    heappush = engine.heapq.heappush

    def recording(heap, item):
        pushes.append(item)
        heappush(heap, item)

    engine.heapq.heappush = recording
    try:
        return sim, sim.run(tenants)
    finally:
        engine.heapq.heappush = heappush


def _digest(sim, results, pushes):
    """Every heap push (time, sequence number, kind, tenant, worker and
    an _ENQUEUE's row costs) and every result field."""
    h = hashlib.sha256()
    for t, seq, kind, qid, who, payload in pushes:
        h.update(np.float64(t).tobytes())
        h.update(np.int64([seq, kind, qid, who]).tobytes())
        if kind == engine._ENQUEUE:
            segs = payload if qid == -1 else [(qid, payload)]
            for q, seg in segs:
                h.update(np.int64(q).tobytes())
                h.update(np.asarray(seg, np.float64).tobytes())
    for r in results:
        for v in (r.latency, r.utilization, r.bytes_moved_remote,
                  r.decision_overhead):
            h.update(np.float64(v).tobytes())
        h.update(np.int64(r.rows_redistributed).tobytes())
        h.update(np.int64(r.num_ticks).tobytes())
        h.update(np.asarray(r.per_worker_busy, np.float64).tobytes())
    return h.hexdigest()[:16]


class TestBranchBitIdentity:
    """Digests taken from the one-group-at-a-time loop, before the
    fan-out was vectorised.  Regenerate ONLY for an intentional change
    of the engine's semantics."""

    PINS = {
        "no_contention": "c5dea8e17f73221e",
        "nic_degrade": "b75e01150b744f0b",
        "nic_degrade_no_contention": "f902e0228e78c834",
        "faults_redirect": "97495b55c514e117",
        "autoscale_redirect": "d081e4271d50ab3b",
        "static_rr": "b26a7204efea53ea",
        "p2c": "3e6b5e5d1e7d2d00",
        "key_affinity": "68a27f661200716a",
        "hillclimb": "bcc1e60be8c9b340",
        "static_rr_coalesced": "da2d671022e7f45a",
        "dyskew_coalesced": "759fe5f69ac0cdbf",
        "dyskew_large_wh": "98691f7d1957dc3f",
    }

    @pytest.mark.parametrize("name", sorted(PINS))
    def test_branch_matches_pin(self, name):
        pushes = []
        sim, results = _run_case(name, pushes)
        # The case really routes across workers (the branch it pins).
        assert sum(r.rows_redistributed for r in results) > 0, name
        assert _digest(sim, results, pushes) == self.PINS[name], name


# ------------------------------------------------------------------ #
# Counters
# ------------------------------------------------------------------ #

class TestRouteCounters:
    COUNTERS = ("route_groups", "route_groups_summed")

    @pytest.mark.parametrize("name", ["dyskew_large_wh", "static_rr_coalesced",
                                      "faults_redirect", "autoscale_redirect"])
    def test_counters_count_the_groups_formed(self, name, monkeypatch):
        formed = []
        group_by_dest = engine._group_by_dest

        def counting(dests, costs, sizes):
            out = group_by_dest(dests, costs, sizes)
            formed.append(out[2] - out[1])      # ends - starts
            return out

        monkeypatch.setattr(engine, "_group_by_dest", counting)
        sim, _ = _run_case(name)
        lens = np.concatenate(formed)
        ev = sim.last_event_counts
        assert ev["route_groups"] == len(lens) > 0
        assert ev["route_groups_summed"] == int((lens >= 8).sum())

    @pytest.mark.parametrize("name", ["dyskew_large_wh", "nic_degrade"])
    def test_counters_same_with_spans_on_and_off(self, name, monkeypatch):
        off, _ = _run_case(name)
        monkeypatch.setattr(Spans, "for_run", classmethod(
            lambda cls: cls(on=True)))
        on, _ = _run_case(name)
        assert any(k.startswith("span_n:") for k in on.last_event_counts)
        for k in self.COUNTERS:
            assert on.last_event_counts[k] == off.last_event_counts[k], k
        assert off.last_event_counts["route_groups"] > 0

    def test_all_local_batches_form_no_group(self):
        t = _tenant("none", SMALL, 7)
        sim = MultiQuerySimulator(SMALL, none_closed_form=False)
        sim.run([t])
        ev = sim.last_event_counts
        assert ev["arrival"] > 0
        assert (ev["route_groups"], ev["route_groups_summed"]) == (0, 0)
