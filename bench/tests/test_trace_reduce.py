"""The reduction from a profiler trace to the device metrics, on a small
trace recorded on one TPU v5e: two jobs of an 8-tenant fleet of the
``tenants512.late`` traffic, each in the harness's job annotation, with
20 ms of sleep between them."""

import gzip
import os

import pytest

from bench import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "testdata", "tenants8.xplane.pb.gz")


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tenants8.xplane.pb"
    with gzip.open(TRACE) as f:
        path.write_bytes(f.read())
    return trace_reduce.read(str(path))


def test_read_finds_the_jobs_and_the_device_ops(raw):
    assert [name for _, _, name in raw["jobs"]] == ["fleet", "fleet"]
    assert list(raw["devices"]) == ["/device:TPU:0"]
    assert len(raw["devices"]["/device:TPU:0"]) == 3026
    assert all(" = " not in name for _, _, name in raw["devices"]["/device:TPU:0"])


def test_busy_and_idle_add_up_to_the_window(raw):
    red = trace_reduce.reduce(raw)
    lo, hi = raw["jobs"][0][0], raw["jobs"][-1][1]
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9, rel=1e-12)
    busy = trace_reduce.union(trace_reduce.clip(
        [(s, e) for s, e, _ in raw["devices"]["/device:TPU:0"]], lo, hi))
    idle = trace_reduce.gaps(busy, lo, hi)
    assert red["busy_s"] == pytest.approx(sum(e - s for s, e in busy) * 1e-9)
    assert red["busy_s"] + sum(e - s for s, e in idle) * 1e-9 == pytest.approx(
        red["window_s"])
    # Recorded numbers of this trace: a few hundred microseconds of device
    # work in 0.29 s.
    assert red["busy_s"] == pytest.approx(0.000314588, rel=1e-9)
    assert red["window_s"] == pytest.approx(0.292274782, rel=1e-9)


def test_breakdown_lists_the_top_ops_and_names_the_longest_gaps(raw):
    red = trace_reduce.reduce(raw)
    ops = red["device_ops"]
    assert len(ops) == 10 and ops[0][0] == "convert_reduce_fusion"
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    gaps = red["idle_gaps"]
    assert len(gaps) == 10
    assert gaps[0][0] == "between jobs: python"
    assert gaps[0][1] == pytest.approx(0.025195246, rel=1e-9)
    assert all(name.startswith("fleet: ") for name, _ in gaps[1:])
    assert any(name == "fleet: np.asarray(jax.Array)" for name, _ in gaps)


def test_interval_helpers():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4), (4, 4.5)]) == [(1, 4.5), (5, 7)]
    assert trace_reduce.clip([(0, 2), (3, 9), (10, 12)], 1, 10) == [(1, 2), (3, 9)]
    assert trace_reduce.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
    assert trace_reduce.short_name("%fusion.9 = (f32[8]) fusion(%a)") == "fusion.9"


def test_nothing_to_reduce_gives_nothing():
    assert trace_reduce.reduce({"jobs": [], "host": [], "devices": {}}) is None
    assert trace_reduce.reduce(
        {"jobs": [(0, 10, "fleet")], "host": [], "devices": {"/device:TPU:0": []}}) is None
