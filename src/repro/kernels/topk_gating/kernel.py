"""Pallas kernel: fused softmax + top-k routing.

The MoE router's softmax→top-k→renormalize sequence runs on every token of
every MoE layer; fusing it avoids three HBM round-trips of the (T, E)
probability matrix.

Tiling: 1-D grid over token blocks; each instance holds a (BLOCK_T, E)
logits tile in VMEM, computes a numerically-stable softmax on the VPU,
then peels off the top-k entries with k iterative argmax+mask passes
(k ≤ 8 everywhere in the assignment, so unrolling is cheap and avoids a
sort network).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gating_kernel(logits_ref, w_ref, idx_ref, *, k: int):
    logits = logits_ref[...].astype(jnp.float32)        # (BLOCK_T, E)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)

    remaining = p
    tot = jnp.zeros((p.shape[0],), jnp.float32)
    ws, idxs = [], []
    for _ in range(k):
        top = jnp.argmax(remaining, axis=-1)            # (BLOCK_T,)
        wv = jnp.max(remaining, axis=-1)
        ws.append(wv)
        idxs.append(top)
        tot = tot + wv
        onehot = (
            jnp.arange(p.shape[-1], dtype=top.dtype)[None, :] == top[:, None]
        )
        remaining = jnp.where(onehot, -1.0, remaining)

    w = jnp.stack(ws, axis=-1)                          # (BLOCK_T, k)
    w = w / jnp.maximum(tot[:, None], 1e-9)             # renormalize
    idx = jnp.stack(idxs, axis=-1).astype(jnp.int32)
    w_ref[...] = w
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def topk_gating(
    logits: jax.Array,    # (T, E)
    *,
    k: int,
    block_t: int = 512,
    interpret: bool = False,
):
    """Returns (weights (T,k) fp32 renormalized, indices (T,k) int32)."""
    T, E = logits.shape
    block_t = min(block_t, T)
    assert T % block_t == 0, (T, block_t)
    return pl.pallas_call(
        functools.partial(_gating_kernel, k=k),
        grid=(T // block_t,),
        in_specs=[pl.BlockSpec((block_t, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.float32),
            jax.ShapeDtypeStruct((T, k), jnp.int32),
        ],
        interpret=interpret,
    )(logits)
