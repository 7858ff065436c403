"""The comparison that decides ``correct``, driven through the rest of a
run (the look for a chip skipped) at a size a test run can hold: sound
runs pass it, the control fails it, and so does the timed path with each
fault a cell can have planted in the program."""

import dataclasses
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench.control import ReferenceProgram

CELLS = ["fig3.dyskew"]


def small_cell(name):
    """The cell on a 2 x 4 warehouse, two passes over a pool of five
    queries."""
    cell = harness.load_cell(name)
    cell["config"]["warehouse"].update(num_nodes=2, interpreters_per_node=4)
    cell["config"]["queries"]["num_queries"] = 5
    cell["traffic"]["passes"] = 2
    cell["traffic"]["compare_queries"] = 3
    return cell


def run(name, seed=2_147_483_659, program_cls=None):
    return harness.run(small_cell(name), seed, 0.05, False, jax.devices(),
                       time.perf_counter(), log=lambda m: None,
                       program_cls=program_cls)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"] and out["failed"] == 0
    assert out["checks"]["result_rel_gap"]["value"] == 0.0
    assert out["checks"]["link_state_mismatch"]["value"] == 0.0
    assert set(out["metrics"]) >= {"rows_per_s", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_control_float32_reference_is_refused(name):
    out = run(name, program_cls=ReferenceProgram)
    assert not out["correct"]


@pytest.fixture
def engine():
    from repro.sim import engine

    return engine


def _state_unchanged(monkeypatch, engine):
    """The link tick returns the state it was given."""
    from repro.core import state_machine
    from repro.sim import batched_link

    def frozen(link, config, **_):
        return link, state_machine.routes_remote(link["state"])

    monkeypatch.setattr(state_machine, "tick_many", frozen)
    monkeypatch.setattr(batched_link._JittedBatchedMachine, "_cache", {})


def _wrap_run(monkeypatch, engine, before=None, after=None):
    orig = engine.MultiQuerySimulator.run

    def run(self, tenants):
        if before is not None:
            tenants = before(tenants)
        results = orig(self, tenants)
        return after(results) if after is not None else results

    monkeypatch.setattr(engine.MultiQuerySimulator, "run", run)


def _half_batch(monkeypatch, engine):
    """Every batch loses its second half of rows."""
    def halve(tenants):
        return [dataclasses.replace(t, streams=[
            [engine.Batch(costs=b.costs[:max(len(b.costs) // 2, 1)],
                          sizes=b.sizes[:max(len(b.sizes) // 2, 1)])
             for b in stream] for stream in t.streams]) for t in tenants]

    _wrap_run(monkeypatch, engine, before=halve)


def _answer_altered(monkeypatch, engine):
    """One query's latency is off by one part in a million."""
    def alter(results):
        results = list(results)
        results[-1] = dataclasses.replace(
            results[-1], latency=results[-1].latency * (1 + 1e-6))
        return results

    _wrap_run(monkeypatch, engine, after=alter)


def _busy_moved(monkeypatch, engine):
    """One row's busy seconds are booked on the wrong worker."""
    def move(results):
        results = list(results)
        busy = np.array(results[0].per_worker_busy, np.float64)
        w = int(np.argmax(busy))
        shift = busy[w] * 1e-3
        busy[w] -= shift
        busy[(w + 1) % len(busy)] += shift
        results[0] = dataclasses.replace(results[0], per_worker_busy=busy)
        return results

    _wrap_run(monkeypatch, engine, after=move)


def _answer_missing(monkeypatch, engine):
    """The last query's answer never comes."""
    _wrap_run(monkeypatch, engine, after=lambda results: list(results)[:-1])


FAULTS = {
    "answer_missing": _answer_missing,
    "state_unchanged": _state_unchanged,
    "half_batch": _half_batch,
    "answer_altered": _answer_altered,
    "busy_moved": _busy_moved,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_refused(name, fault, monkeypatch, engine):
    FAULTS[fault](monkeypatch, engine)
    out = run(name)
    assert not out["correct"]
