"""Pallas kernel: destination-load histogram (bincount).

Every DySkew decision consumes per-destination load counts — expert loads
in the MoE dispatch, per-shard token counts in the data path.  This kernel
computes ``counts[e] = |{i : ids[i] == e}|`` for E destinations.

Tiling: 1-D grid over id blocks; all grid steps accumulate into the same
(E,) output block (Pallas guarantees sequential grid order on TPU, so the
read-modify-write accumulation is safe).  Each block materializes a
(BLOCK_N, E) one-hot tile in VMEM — for E ≤ 512 and BLOCK_N = 1024 that is
≤ 2 MB fp32, well within budget, and the compare+reduce maps onto the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(ids_ref, out_ref, *, num_dest: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                                # (BLOCK_N,)
    onehot = (
        ids[:, None] == jnp.arange(num_dest, dtype=ids.dtype)[None, :]
    ).astype(jnp.float32)
    out_ref[...] += jnp.sum(onehot, axis=0)


@functools.partial(jax.jit, static_argnames=("num_dest", "block_n", "interpret"))
def load_histogram(
    ids: jax.Array,       # (N,) int32 in [0, num_dest)
    *,
    num_dest: int,
    block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Returns (num_dest,) float32 counts."""
    N = ids.shape[0]
    block_n = min(block_n, N)
    assert N % block_n == 0, (N, block_n)
    return pl.pallas_call(
        functools.partial(_hist_kernel, num_dest=num_dest),
        grid=(N // block_n,),
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,))],
        out_specs=pl.BlockSpec((num_dest,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((num_dest,), jnp.float32),
        interpret=interpret,
    )(ids.astype(jnp.int32))
