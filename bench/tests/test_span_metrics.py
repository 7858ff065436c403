"""The per-layer readers of the program's spans (``span_ns:`` /
``span_n:`` in ``last_event_counts``), on synthetic observations."""

import pytest

from bench import harness

ROWS = 400_000
COUNTS = {
    "heap_events": 200_000, "tick": 0, "gtick": 100,
    "span_n:dyskew.run": 20, "span_ns:dyskew.run": 2_000_000_000,
    "span_n:dyskew.setup": 20, "span_ns:dyskew.setup": 40_000_000,
    "span_n:dyskew.loop": 20, "span_ns:dyskew.loop": 1_800_000_000,
    "span_n:dyskew.route": 5_000, "span_ns:dyskew.route": 200_000_000,
    "span_n:dyskew.tick": 100, "span_ns:dyskew.tick": 10_000_000,
    "span_n:dyskew.tick.wait": 100, "span_ns:dyskew.tick.wait": 4_000_000,
    "span_n:dyskew.drain": 20, "span_ns:dyskew.drain": 30_000_000,
    "event_ns:enqueue": 1_000_000_000,
}
WANT = {
    "setup_us_per_job": 2000.0,            # 40 ms over 20 jobs
    "loop_self_us_per_krow": 3975.0,       # (1800 - 200 - 10) ms over 400 krow
    "route_us_per_krow": 500.0,
    "tick_host_us_per_call": 60.0,         # (10 - 4) ms over 100 calls
    "tick_wait_us_per_call": 40.0,
    "drain_us_per_krow": 75.0,
    "unspanned_share": 100.0 / 21.0,       # 2.0 s of spans in a 2.1 s window
}


def _obs(counts=COUNTS, trace=True):
    obs = {"traced": {"rows": ROWS, "counts": dict(counts)}}
    if trace:
        obs["trace"] = {"busy_s": 0.001, "window_s": 2.1}
    return obs


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_arithmetic(metric):
    assert harness._load_reader(metric)(_obs()) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_reads_nothing_without_spans(metric):
    read = harness._load_reader(metric)
    assert read({}) is None
    plain = {k: v for k, v in COUNTS.items() if not k.startswith(("span_", "event_"))}
    # The untraced run, or a program that records no span.
    assert read(_obs(plain)) is None


@pytest.mark.parametrize("metric", ["tick_host_us_per_call", "tick_wait_us_per_call"])
def test_no_tick_span_reads_nothing_per_call(metric):
    counts = {k: v for k, v in COUNTS.items() if "dyskew.tick" not in k}
    assert harness._load_reader(metric)(_obs(counts)) is None


@pytest.mark.parametrize("metric", ["drain_us_per_krow", "route_us_per_krow"])
def test_a_layer_never_entered_reads_zero(metric):
    layer = {"drain_us_per_krow": "drain", "route_us_per_krow": "route"}[metric]
    counts = {k: v for k, v in COUNTS.items() if f"dyskew.{layer}" not in k}
    assert harness._load_reader(metric)(_obs(counts)) == 0.0


def test_unspanned_share_needs_the_trace_window():
    read = harness._load_reader("unspanned_share")
    assert read(_obs(trace=False)) is None
    obs = _obs()
    obs["trace"] = None
    assert read(obs) is None


def test_every_span_reader_is_a_per_layer_metric_of_the_cell():
    cell = harness.load_cell("fig3.dyskew")
    mine = {m["name"]: m for m in cell["per_layer"]}
    for name in WANT:
        m = mine[name]
        assert m["source"] == "program_span" and m["moves"] == "rows_per_s"
        assert m["better"] == "lower"
