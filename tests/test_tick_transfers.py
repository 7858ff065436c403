"""The batched tick driver's host→device traffic (`BatchedLinkSim`).

  * Bit-identity: the packed one-array input gives the states and masks
    a direct jitted `state_machine.tick_many` call on the six separate
    float32 / bool inputs gives, every tick, for padded and unpadded
    tenant counts, inactive rows, an (n,) signal and zero-row ticks.
  * Transfers: every call of the jitted tick gets exactly one host
    array (the packed input) and device arrays otherwise, whether the
    transfer is explicit or implicit; a driver of a shape already built
    is made without any transfer, and drivers of one shape share the
    cached initial state.
"""

from functools import partial

import jax
import numpy as np
import pytest

from repro.core import state_machine
from repro.core.types import DySkewConfig, Policy, SkewModelKind, link_state_init
from repro.sim import batched_link
from repro.sim.batched_link import BatchedLinkSim
from repro.sim.engine import ClusterConfig, MultiQuerySimulator, TenantQuery
from repro.sim.replay import dyskew_strategy, scan_arrival_gap
from repro.sim.workload import customer_replay_suite, generate_query

CFG = DySkewConfig(policy=Policy.EAGER_SNOWPARK,
                   skew_model=SkewModelKind.IDLE_TIME, n_strikes=2)
N = 16


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        jax.device_get(tree))]


def _assert_same(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)


def _inputs(rng, t, n, signal_1d):
    rows = (rng.poisson(3, (t, n)) * (rng.random((t, n)) < 0.7)).astype(
        np.float64)
    batches = rng.integers(0, 3, (t, n)).astype(np.float64)
    sync = rng.random((t, n)) * rows * 1e-3
    density = np.where(batches > 0, rows / np.maximum(batches, 1), 0.0)
    bpr = np.where(rows > 0, rng.random((t, n)) * 2e6 / np.maximum(rows, 1),
                   0.0)
    shape = (n,) if signal_1d else (t, n)
    signal = rng.random(shape) < 0.3
    active = rng.random(t) < 0.8
    return rows, sync, density, bpr, signal, active


@partial(jax.jit, static_argnames="cfg")
def _direct(link, rows, sync, density, bpr, signal, active, *, cfg):
    return state_machine.tick_many(
        link, cfg, rows_this_tick=rows, sync_time_this_tick=sync,
        batch_density=density, bytes_per_row=bpr, signal_this_tick=signal,
        active=active,
    )


@pytest.mark.parametrize("cfg", [
    CFG,
    DySkewConfig(policy=Policy.LATE, skew_model=SkewModelKind.IDLE_TIME,
                 n_strikes=2),
    DySkewConfig(policy=Policy.LATE,
                 skew_model=SkewModelKind.SYNC_TIME_SLOPE, n_strikes=2),
], ids=["eager_idle", "late_idle", "late_slope"])
@pytest.mark.parametrize("t", [1, 3, 8])
def test_packed_tick_is_bit_identical_to_tick_many(cfg, t):
    rng = np.random.default_rng(100 + t)
    sim = BatchedLinkSim(cfg, N, t)
    ref = jax.tree_util.tree_map(
        lambda x: np.repeat(np.asarray(x)[None], t, axis=0),
        link_state_init(N, cfg),
    )
    got = jax.tree_util.tree_map(lambda x: np.asarray(x)[:t],
                                 jax.device_get(sim.state))
    _assert_same(got, ref)
    for k in range(40):
        rows, sync, density, bpr, signal, active = _inputs(
            rng, t, N, signal_1d=k % 2 == 0)
        if k % 7 == 3:  # a tick in which no row arrived anywhere
            rows[:] = sync[:] = density[:] = bpr[:] = 0.0
        mask = sim.tick(rows, sync, density, bpr, signal, active)
        ref, ref_mask = _direct(
            ref, rows.astype(np.float32), sync.astype(np.float32),
            density.astype(np.float32), bpr.astype(np.float32),
            np.broadcast_to(signal, (t, N)), active, cfg=cfg,
        )
        assert mask.shape == (t, N) and mask.dtype == bool
        assert np.array_equal(mask, np.asarray(ref_mask)), k
        assert not mask[~active].any()
        got = jax.tree_util.tree_map(lambda x: np.asarray(x)[:t],
                                     jax.device_get(sim.state))
        _assert_same(got, ref)


class _HostArgs:
    """Wraps each jitted tick `_JittedBatchedMachine.get` hands out and
    records, per call, how many of its argument leaves are host arrays
    (each one a host→device transfer) rather than device arrays."""

    def __init__(self, monkeypatch):
        self.per_call = []
        get = batched_link._JittedBatchedMachine.get

        def spied(cfg, capacity, n):
            fn, state = get(cfg, capacity, n)
            return self._wrap(fn), state

        monkeypatch.setattr(batched_link._JittedBatchedMachine, "get",
                            staticmethod(spied))

    def _wrap(self, fn):
        def tick(*args):
            leaves = jax.tree_util.tree_leaves(args)
            self.per_call.append(
                sum(not isinstance(x, jax.Array) for x in leaves))
            return fn(*args)
        return tick


def test_each_tick_hands_the_jit_one_host_array(monkeypatch):
    rng = np.random.default_rng(5)
    host = _HostArgs(monkeypatch)
    for t in (1, 3, 8):
        sim = BatchedLinkSim(CFG, N, t)
        for k in range(5):
            sim.tick(*_inputs(rng, t, N, signal_1d=k % 2 == 0))
    assert host.per_call == [1] * 15


def test_driver_of_a_built_shape_is_made_without_a_transfer():
    rng = np.random.default_rng(5)
    BatchedLinkSim(CFG, N, 2).tick(*_inputs(rng, 2, N, False))
    with jax.transfer_guard("disallow"):
        sim = BatchedLinkSim(CFG, N, 2)
    assert all(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(sim.state))


def test_drivers_of_one_shape_share_the_initial_state():
    a, b = BatchedLinkSim(CFG, N, 4), BatchedLinkSim(CFG, N, 3)
    assert a.capacity == b.capacity == 4
    start = b.state
    for x, y in zip(jax.tree_util.tree_leaves(a.state),
                    jax.tree_util.tree_leaves(start)):
        assert x is y
    rng = np.random.default_rng(9)
    for _ in range(6):
        a.tick(*_inputs(rng, 4, N, False))
    assert np.asarray(a.ticks).any()
    assert b.state is start
    assert all(not np.asarray(x).any() for x in _leaves(start))
    assert all(not np.asarray(x).any()
               for x in _leaves(BatchedLinkSim(CFG, N, 4).state))


def test_run_transfers_one_array_per_group_tick(monkeypatch):
    monkeypatch.setattr(batched_link._JittedBatchedMachine, "_cache", {})
    built = []
    zero = batched_link._zero_link_state
    monkeypatch.setattr(batched_link, "_zero_link_state",
                        lambda *a: built.append(a) or zero(*a))
    host = _HostArgs(monkeypatch)
    cluster = ClusterConfig(num_nodes=8)
    prof = customer_replay_suite(num_queries=3)[1]
    tenant = TenantQuery(
        name="fig3", streams=generate_query(prof, cluster.num_workers,
                                            seed=11),
        strategy=dyskew_strategy(prof),
        arrival_gap=scan_arrival_gap(prof, cluster),
    )
    for run in range(2):
        del host.per_call[:]
        sim = MultiQuerySimulator(cluster)
        sim.run([tenant])
        counts = sim.last_event_counts
        assert counts["gtick"] > 0 and counts["tick"] == 0
        assert host.per_call == [1] * counts["gtick"], run
    # The second run's driver started from the first run's state.
    assert len(built) == 1
