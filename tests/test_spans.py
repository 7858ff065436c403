"""The spans and self-time counters of `MultiQuerySimulator.run`
(`repro.sim.spans`): off without a profiler session, bit-identical
results with one, and on the profiler's host plane nested as the
benchmark's per-layer metrics read them."""

import dataclasses

import jax
import numpy as np
import pytest

from bench import trace_reduce
from repro.sim import spans as spans_mod
from repro.sim.engine import (
    Batch,
    ClusterConfig,
    MultiQuerySimulator,
    StrategyConfig,
    TenantQuery,
)
from repro.sim.replay import dyskew_strategy, scan_arrival_gap
from repro.sim.spans import Spans
from repro.sim.workload import QueryProfile, generate_query

CLUSTER = ClusterConfig(num_nodes=2)

#: Each span and the span it sits in (None: the run itself).
PARENT = {
    "dyskew.run": None,
    "dyskew.setup": "dyskew.run",
    "dyskew.loop": "dyskew.run",
    "dyskew.drain": "dyskew.run",
    "dyskew.finish": "dyskew.run",
    "dyskew.route": "dyskew.loop",
    "dyskew.tick": "dyskew.loop",
    "dyskew.tick.wait": "dyskew.tick",
}
PHASES = ("dyskew.setup", "dyskew.loop", "dyskew.drain", "dyskew.finish")
SPAN_KEYS = ("span_ns:", "span_n:", "event_ns:")


def _query():
    prof = QueryProfile(
        name="spans", n_rows=8000, mean_row_cost=1e-3, cost_sigma=1.0,
        partition_alpha=0.8, hot_fraction=0.2,
    )
    batches = generate_query(prof, CLUSTER.num_workers, seed=7)
    return [TenantQuery("spans", batches, dyskew_strategy(prof), 0.0,
                        scan_arrival_gap(prof, CLUSTER))]


def _answers(sim, results):
    return ([dataclasses.asdict(r) for r in results],
            [jax.tree_util.tree_map(np.asarray, jax.device_get(s))
             for s in sim.last_link_states],
            dict(sim.last_event_counts))


@pytest.fixture(scope="module", params=[None, False],
                ids=["batched_driver", "per_query_driver"])
def runs(request, tmp_path_factory):
    """One query run without the profiler and once under it, inside the
    harness's job annotation, through either tick driver."""
    tenants = _query()
    plain = MultiQuerySimulator(CLUSTER, batch_ticks=request.param)
    off = _answers(plain, plain.run(tenants))
    log_dir = str(tmp_path_factory.mktemp("spans_trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    traced = MultiQuerySimulator(CLUSTER, batch_ticks=request.param)
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(trace_reduce.JOB_SPAN, name="query"):
            results = traced.run(tenants)
    on = _answers(traced, results)
    raw = trace_reduce.read(trace_reduce.find_xplane(log_dir))
    spans = sorted((s, e, name) for s, e, name in raw["host"]
                   if name.startswith("dyskew."))
    return {"off": off, "on": on, "raw": raw, "spans": spans}


def test_profiler_off_adds_no_span_counter(runs):
    counts = runs["off"][2]
    assert counts["drain_entered"] == 1 and counts["heap_events"] > 0
    assert not [k for k in counts if k.startswith(SPAN_KEYS)]


def test_tracing_leaves_results_states_and_counters_untouched(runs):
    (res_off, links_off, counts_off), (res_on, links_on, counts_on) = (
        runs["off"], runs["on"])
    assert len(res_off) == len(res_on)
    for a, b in zip(res_off, res_on):
        assert a.keys() == b.keys()
        for k in a:
            assert np.array_equal(a[k], b[k]), k
    assert len(links_off) == len(links_on)
    for a, b in zip(links_off, links_on):
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert x.dtype == y.dtype and np.array_equal(x, y)
    assert {k: counts_on[k] for k in counts_off} == counts_off
    assert all(k.startswith(SPAN_KEYS) for k in set(counts_on) - set(counts_off))


def test_spans_sit_on_the_calling_thread_nested_as_in_the_table(runs):
    spans = runs["spans"]
    # Only the host line that holds the job annotation is read, so every
    # span found was recorded on the thread that called `run`.
    assert {name for _, _, name in spans} == set(PARENT)
    for s, e, name in spans:
        around = [(s2, e2, n2) for s2, e2, n2 in spans
                  if (s2, e2, n2) != (s, e, name) and s2 <= s and e <= e2]
        inner = min(around, key=lambda x: x[1] - x[0]) if around else None
        assert (inner[2] if inner else None) == PARENT[name], (name, inner)


def test_span_counts_match_the_trace_and_the_event_counters(runs):
    counts = runs["on"][2]
    in_trace = {}
    for _, _, name in runs["spans"]:
        in_trace[name] = in_trace.get(name, 0) + 1
    assert {n: counts[f"span_n:{n}"] for n in in_trace} == in_trace
    assert counts["span_n:dyskew.tick"] == counts["tick"] + counts["gtick"]
    routed = (counts["arrival_runs_coalesced"] + counts["arrival"]
              + counts["admitted"] - counts["arrivals_in_runs"])
    assert counts["span_n:dyskew.route"] == routed
    kinds = {k.split(":", 1)[1] for k in counts if k.startswith("event_ns:")}
    assert kinds >= {"arrival", "enqueue", "done"}
    assert all(counts[f"event_ns:{k}"] > 0 and counts[k] > 0 for k in kinds)


def test_children_never_exceed_their_parent(runs):
    counts = runs["on"][2]

    def ns(name):
        return counts.get(f"span_ns:{name}", 0)

    for parent in set(PARENT.values()) - {None}:
        children = [n for n, p in PARENT.items() if p == parent]
        assert sum(ns(n) for n in children) <= ns(parent), parent
    assert sum(v for k, v in counts.items()
               if k.startswith("event_ns:")) <= ns("dyskew.loop")


def test_phases_cover_the_run(runs):
    counts = runs["on"][2]
    phases = sum(counts[f"span_ns:{n}"] for n in PHASES)
    assert phases >= 0.99 * counts["span_ns:dyskew.run"]


def test_a_gap_inside_the_loop_is_named_by_a_span(runs):
    spans = runs["spans"]
    (lo, hi, _), = [x for x in spans if x[2] == "dyskew.loop"]
    kids = sorted((s, e) for s, e, n in spans
                  if n in ("dyskew.route", "dyskew.tick"))
    # The longest stretch of the loop between two routed arrivals or
    # ticks: enqueue and completion handling, as an idle gap of the
    # device would see it.
    edges = [lo] + [x for s, e in kids for x in (s, e)] + [hi]
    s, e = max(zip(edges[::2], edges[1::2]), key=lambda g: g[1] - g[0])
    assert e > s
    assert trace_reduce.name_gap(runs["raw"], s, e) == "query: dyskew.loop"


def test_closed_form_none_path_is_set_up_then_drain(tmp_path):
    rng = np.random.default_rng(3)
    streams = [[Batch(costs=rng.uniform(1e-4, 1e-3, 40), sizes=np.full(40, 100.0))]
               for _ in range(CLUSTER.num_workers)]
    tenants = [TenantQuery("none", streams, StrategyConfig(kind="none"))]
    plain = MultiQuerySimulator(CLUSTER)
    off = _answers(plain, plain.run(tenants))
    traced = MultiQuerySimulator(CLUSTER)
    with jax.profiler.trace(str(tmp_path)):
        on = _answers(traced, traced.run(tenants))
    counts = on[2]
    assert counts["none_closed_form_tenants"] == 1
    assert {k for k in counts if k.startswith("span_n:")} == {
        "span_n:dyskew.run", "span_n:dyskew.setup", "span_n:dyskew.drain"}
    assert (counts["span_ns:dyskew.setup"] + counts["span_ns:dyskew.drain"]
            <= counts["span_ns:dyskew.run"])
    assert [r["latency"] for r in on[0]] == [r["latency"] for r in off[0]]


def test_recorder_is_off_outside_a_run_and_shared_off_records_nothing(runs):
    assert not Spans.for_run().on
    assert spans_mod.OFF.ns == {} and spans_mod.OFF.event_ns == {}
    assert spans_mod.OFF.span("dyskew.x") is spans_mod.OFF.span("dyskew.y")


def test_phases_follow_one_another_and_count():
    rec = Spans(on=True)
    rec.phase("a")
    rec.phase("b")
    with rec.span("c"):
        pass
    rec.phase(None)
    rec.event_ns = {"enqueue": 5}
    counts = rec.counts()
    assert {k: counts[k] for k in counts if k.startswith("span_n:")} == {
        "span_n:a": 1, "span_n:b": 1, "span_n:c": 1}
    assert all(counts[f"span_ns:{n}"] >= 0 for n in "abc")
    assert counts["event_ns:enqueue"] == 5
