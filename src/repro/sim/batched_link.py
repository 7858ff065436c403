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


def _stacked_host_link_state(
    capacity: int, n: int, cfg: DySkewConfig
) -> Dict[str, np.ndarray]:
    """Host-numpy (T, ...) stack of `types.link_state_init` trees: one
    row per tenant slot, same leaves and dtypes by construction (derived
    from the canonical tree, so a new metric leaf cannot silently desync
    the batched layout), no device round-trip.  Valid because every leaf
    of the initial state is zero (LinkState.INIT == 0)."""
    template = link_state_init(n, cfg)
    return jax.tree_util.tree_map(
        lambda x: np.zeros((capacity,) + np.shape(x), x.dtype), template
    )


def _batched_tick_impl(link, rows, sync, density, bpr, signal, active, *, cfg):
    return state_machine.tick_many(
        link,
        cfg,
        rows_this_tick=rows,
        sync_time_this_tick=sync,
        batch_density=density,
        bytes_per_row=bpr,
        signal_this_tick=signal,
        active=active,
    )


class _JittedBatchedMachine:
    """Caches one jitted `state_machine.tick_many` per (config, T, n)."""

    _cache: Dict[Tuple, Callable] = {}

    @classmethod
    def get(cls, cfg: DySkewConfig, capacity: int, n: int) -> Callable:
        key = (cfg, capacity, n)
        fn = cls._cache.get(key)
        if fn is None:
            fn = jax.jit(partial(_batched_tick_impl, cfg=cfg))
            cls._cache[key] = fn
        return fn


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
        self.state = _stacked_host_link_state(self.capacity, n, cfg)
        self._tick = _JittedBatchedMachine.get(cfg, self.capacity, n)

    def _pad(self, x: np.ndarray, dtype) -> np.ndarray:
        t = len(x)
        if t == self.capacity:
            return np.asarray(x, dtype)
        out = np.zeros((self.capacity,) + np.shape(x)[1:], dtype)
        out[:t] = x
        return out

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
        distribute mask (False rows for inactive tenants)."""
        t = self.num_tenants
        signal = np.asarray(signal, bool)
        if signal.ndim == 1:
            signal = np.broadcast_to(signal, (t, self.n))
        self.state, distribute = self._tick(
            self.state,
            self._pad(rows, np.float32),
            self._pad(sync, np.float32),
            self._pad(density, np.float32),
            self._pad(bpr, np.float32),
            self._pad(signal, bool),
            self._pad(active, bool),
        )
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
