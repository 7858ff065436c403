"""Pallas kernel: Mamba-2 inter-chunk state recurrence.

The chunked SSD algorithm reduces each chunk to a (H, P, N) state
contribution plus a per-head decay; chaining them is a strictly sequential
recurrence over chunks:

    h_c = decay_c ⊙ h_{c-1} + s_c            (prefix of h fed back to chunk c)

This kernel computes all prefix states in one pass.  Tiling: grid over
(head blocks × P blocks); each program instance keeps its (C, BLOCK_H,
BLOCK_P, N) slice of the contributions in VMEM and walks the C chunks with
a fori_loop — the recurrence is latency-bound, so the win is keeping the
whole walk on-chip instead of C round-trips to HBM.  The default blocks
keep the double-buffered input and output slices within the 16 MiB scoped
VMEM limit at C=32, N=128 (4 × 2 MiB), and carry BLOCK_H (BLOCK_P, N)
states of 8 vregs each through the loop.

The (C, H) decays are scalar-prefetched into SMEM and read one scalar per
(chunk, head): a (C, BLOCK_H) VMEM block would have a last dimension that
is neither a multiple of 128 nor the full H, which Mosaic refuses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_scan_kernel(decay_ref, states_ref, out_ref):
    """decay_ref: (C, H) in SMEM; states_ref: (C, BH, BP, N); out_ref like
    states.

    out[c] = prefix state BEFORE chunk c (h_{c-1} in the recurrence).
    """
    C, BH = states_ref.shape[:2]
    h_base = pl.program_id(0) * BH
    h0 = tuple(
        jnp.zeros(states_ref.shape[2:], jnp.float32) for _ in range(BH)
    )

    def body(c, h):
        nxt = []
        for b in range(BH):
            out_ref[c, b] = h[b]
            s = states_ref[c, b].astype(jnp.float32)      # (BP, N)
            nxt.append(h[b] * decay_ref[c, h_base + b] + s)
        return tuple(nxt)

    jax.lax.fori_loop(0, C, body, h0)


@functools.partial(jax.jit, static_argnames=("block_h", "block_p", "interpret"))
def ssd_state_scan(
    states: jax.Array,    # (C, H, P, N) per-chunk contributions
    decay: jax.Array,     # (C, H) per-chunk decays (exp of summed dA)
    *,
    block_h: int = 2,
    block_p: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Returns (C, H, P, N): the state entering each chunk."""
    C, H, P, N = states.shape
    block_h = min(block_h, H)
    block_p = min(block_p, P)
    assert H % block_h == 0 and P % block_p == 0
    block = (C, block_h, block_p, N)
    return pl.pallas_call(
        _ssd_scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // block_h, P // block_p),
            in_specs=[pl.BlockSpec(block, lambda i, j, _: (0, i, j, 0))],
            out_specs=pl.BlockSpec(block, lambda i, j, _: (0, i, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((C, H, P, N), jnp.float32),
        interpret=interpret,
    )(decay.astype(jnp.float32), states)
