"""Batched adaptive-link driver: ONE jitted call ticks every tenant.

The per-tenant `AdaptiveLinkSim` in `repro.sim.engine` pays one jit
dispatch per tenant per metrics tick, so the tick overhead of
`MultiQuerySimulator.run` grows linearly with the number of concurrent
queries and dominates the event loop at N≳64 tenants.  This module holds
the scaling fix: all tenants' link state is stacked into a single
``(T, n)`` array pytree (T tenants × n sibling link instances) and the
whole fleet advances through ONE jitted `state_machine.tick_many` call
per shared virtual-time tick.

Key properties:

  * Fixed-capacity padding.  ``BatchedLinkSim`` rounds its tenant
    capacity up to a power of two and masks the unused rows, so the jit
    cache (keyed on (config, capacity, n)) is hit across suites with
    different tenant counts instead of recompiling per count.
  * Inactive-row masking.  A (T,) ``active`` mask freezes the state of
    tenants that have not arrived yet (or have drained) bit-for-bit and
    forces their distribute mask to False — the event loop keeps ONE
    shared tick cadence and simply masks who participates.
  * Bit-exact rows.  ``jax.vmap`` of the per-tenant tick is bit-identical
    per row to the unbatched `AdaptiveLinkSim` call on the reductions
    involved (sibling sums over n, window sums over W).  Combined with
    on-grid arrivals (every member's arrival an exact value of the
    group's chained tick grid — identical arrivals are the trivial
    case), the whole multi-link group ticks at precisely its per-tenant
    instants, which is why the engine's auto default batches such
    groups without disturbing the `tests/test_sim_equivalence.py` pin;
    see `engine._arrivals_on_grid` for the envelope check.
    `tests/test_batched_link.py` asserts state-for-state equality against
    T independent `AdaptiveLinkSim` instances across mixed cadences.
  * One transfer per tick.  The six per-tick inputs reach the jitted
    tick as one packed float32 host array, its only host argument, and
    every driver of a (config, T, n) shape starts from that shape's
    cached all-zero state on the device, so building a driver transfers
    nothing (`tests/test_tick_transfers.py`).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Tuple

import jax
import numpy as np

from repro.core import state_machine
from repro.core.types import DySkewConfig, link_state_init
from repro.sim.spans import OFF, Spans


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length() if x > 1 else 1


def _link_state_shapes(capacity: int, n: int, cfg: DySkewConfig) -> Dict:
    """Shapes and dtypes of a (T, ...) stack of `types.link_state_init`
    trees, one row per tenant slot: the same leaves by construction, so
    a new metric leaf cannot silently desync the batched layout."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((capacity,) + x.shape, x.dtype),
        jax.eval_shape(partial(link_state_init, n, cfg)),
    )


def _zero_link_state(capacity: int, n: int, cfg: DySkewConfig) -> Dict:
    """That stack's initial state, on the device.  Valid because every
    leaf of the initial state is zero (LinkState.INIT == 0)."""
    return jax.device_put(jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape, x.dtype),
        _link_state_shapes(capacity, n, cfg),
    ))


#: Rows of the packed per-tick input: rows, sync, density, bytes per row,
#: signal (0/1) and active (0/1, broadcast over the n instances).
_PACKED_ROWS = 6


def _batched_tick_impl(link, packed, *, cfg):
    """Unpack the (T, 6, n) float32 tick input and advance every row."""
    return state_machine.tick_many(
        link,
        cfg,
        rows_this_tick=packed[:, 0],
        sync_time_this_tick=packed[:, 1],
        batch_density=packed[:, 2],
        bytes_per_row=packed[:, 3],
        signal_this_tick=packed[:, 4] != 0,
        active=packed[:, 5, 0] != 0,
    )


class _JittedBatchedMachine:
    """Caches, per (config, T, n), one jitted `state_machine.tick_many`
    and that shape's all-zero initial state on the device.  Every driver
    of the shape starts from the one state: JAX arrays are immutable and
    the tick donates nothing, so sharing it is safe."""

    _cache: Dict[Tuple, Tuple[Callable, Dict]] = {}

    @classmethod
    def get(cls, cfg: DySkewConfig, capacity: int, n: int):
        """(jitted tick, initial state) of the shape."""
        key = (cfg, capacity, n)
        hit = cls._cache.get(key)
        if hit is None:
            hit = cls._cache[key] = (
                jax.jit(partial(_batched_tick_impl, cfg=cfg)),
                _zero_link_state(capacity, n, cfg),
            )
        return hit


class BatchedLinkSim:
    """Host-side wrapper advancing the link state machines of T tenants
    (each with n sibling producer link instances) in ONE jitted call.

    The drop-in batched counterpart of `engine.AdaptiveLinkSim`: tenant
    row ``i`` of a tick is bit-identical to what an independent
    `AdaptiveLinkSim` fed the same per-tick inputs would produce, and
    rows masked inactive do not advance at all.
    """

    #: Span recorder; the run that builds the driver hands it its own.
    spans: Spans = OFF

    def __init__(self, cfg: DySkewConfig, n: int, num_tenants: int):
        self.cfg = cfg
        self.n = n
        self.num_tenants = num_tenants
        # Pad to a power of two so differently-sized suites share compiles.
        self.capacity = _next_pow2(num_tenants)
        self._tick, self.state = _JittedBatchedMachine.get(
            cfg, self.capacity, n
        )

    def tick(
        self,
        rows: np.ndarray,      # (T, n) float
        sync: np.ndarray,      # (T, n) float
        density: np.ndarray,   # (T, n) float
        bpr: np.ndarray,       # (T, n) float
        signal: np.ndarray,    # (T, n) or (n,) bool
        active: np.ndarray,    # (T,) bool
    ) -> np.ndarray:
        """Advance the active tenants one tick; returns the (T, n) bool
        distribute mask (False rows for inactive tenants).

        The six inputs travel as ONE (capacity, 6, n) float32 array, the
        jitted tick's only host argument; the float64→float32 casts
        happen on assignment, as a cast would make them, and padded rows
        stay zero (inactive).  The array is fresh each tick: a backend
        may alias a host buffer it is given."""
        t = self.num_tenants
        packed = np.zeros((self.capacity, _PACKED_ROWS, self.n), np.float32)
        packed[:t, 0] = rows
        packed[:t, 1] = sync
        packed[:t, 2] = density
        packed[:t, 3] = bpr
        packed[:t, 4] = np.asarray(signal, bool)
        packed[:t, 5] = np.asarray(active, bool)[:, None]
        self.state, distribute = self._tick(self.state, packed)
        with self.spans.span("dyskew.tick.wait"):
            return np.asarray(distribute)[:t]

    @property
    def states(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.state["state"]))[:self.num_tenants]

    @property
    def transitions(self) -> np.ndarray:
        return np.asarray(
            jax.device_get(self.state["transitions"])
        )[:self.num_tenants]

    @property
    def ticks(self) -> np.ndarray:
        """Per-tenant count of (unmasked) ticks applied."""
        return np.asarray(jax.device_get(self.state["tick"]))[:self.num_tenants]
