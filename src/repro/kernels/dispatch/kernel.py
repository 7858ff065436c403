"""Pallas kernel: routing-plan gather — the redistribution data movement.

Given activations ``x`` (T, D), a slot→source-token map ``src`` (S,) and a
slot validity mask, produce the dispatch buffer (S, D) with invalid slots
zeroed.  This is the hot inner loop of DySkew's redistribution on TPU: the
(G, E, C, d) MoE dispatch buffer and the serving-side request migration
buffers are both built from this primitive.

Tiling: grid over (slot blocks × feature blocks).  Each program instance
holds one (BLOCK_S, BLOCK_D) output tile and the full (T, BLOCK_D) stripe
of ``x`` in VMEM; rows are fetched with dynamic slices.  BLOCK_D is chosen
so the double-buffered stripe and its float32 copy fit scoped VMEM
(T·BLOCK_D·(2·2 + 4) bytes = 4 MiB at T=1024, BLOCK_D=512 for bf16); the
MXU is not involved (pure data movement) so lane alignment (128) is what
matters.

Mosaic constraints that shape the kernel:

  * ``src`` and ``valid`` are scalar-prefetched into SMEM: the loop reads
    one scalar per slot, and a dynamic scalar read from a 1-D VMEM block
    is refused (the index cannot be proven tile-aligned).
  * A single row at a dynamic offset can be loaded or stored only in a
    32-bit layout (16-bit dtypes pack two rows per sublane).  The stripe
    is therefore staged in a float32 scratch, rows are gathered into a
    float32 tile, and the tile is cast back to ``x.dtype`` in one aligned
    store.  Every cast is exact, so the result equals the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dispatch_kernel(src_ref, valid_ref, x_ref, out_ref, x32_ref, tile_ref):
    """One (BLOCK_S, BLOCK_D) output tile.

    src_ref:   (S,) int32 in SMEM — source row per slot
    valid_ref: (S,) int32 in SMEM — 1 if the slot is filled
    x_ref:     (T, BLOCK_D)      — feature stripe of the source tokens
    out_ref:   (BLOCK_S, BLOCK_D)
    x32_ref:   (T, BLOCK_D) float32 scratch — the stripe, widened
    tile_ref:  (BLOCK_S, BLOCK_D) float32 scratch — the gathered rows
    """
    block_s = out_ref.shape[0]
    base = pl.program_id(0) * block_s
    x32_ref[...] = x_ref[...].astype(jnp.float32)

    def body(i, _):
        idx = src_ref[base + i]
        v = valid_ref[base + i]
        row = x32_ref[pl.dslice(idx, 1), :]
        tile_ref[pl.dslice(i, 1), :] = row * v.astype(jnp.float32)
        return 0

    jax.lax.fori_loop(0, block_s, body, 0)
    out_ref[...] = tile_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_d", "interpret")
)
def dispatch_gather(
    x: jax.Array,        # (T, D)
    src: jax.Array,      # (S,) int32 in [0, T)
    valid: jax.Array,    # (S,) bool/int
    *,
    block_s: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns (S, D) dispatch buffer; invalid slots are zero."""
    T, D = x.shape
    S = src.shape[0]
    block_s = min(block_s, S)
    block_d = min(block_d, D)
    assert S % block_s == 0 and D % block_d == 0, (S, block_s, D, block_d)

    return pl.pallas_call(
        _dispatch_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S // block_s, D // block_d),
            in_specs=[pl.BlockSpec((T, block_d), lambda i, j, *_: (0, j))],
            out_specs=pl.BlockSpec(
                (block_s, block_d), lambda i, j, *_: (i, j)
            ),
            scratch_shapes=[
                pltpu.VMEM((T, block_d), jnp.float32),
                pltpu.VMEM((block_s, block_d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, D), x.dtype),
        interpret=interpret,
    )(src.astype(jnp.int32), valid.astype(jnp.int32), x)
