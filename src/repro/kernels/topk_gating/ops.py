"""Jit'd public wrapper for fused top-k gating."""

from repro.kernels.topk_gating.kernel import topk_gating
from repro.kernels.topk_gating.ref import topk_gating_ref


def gating(logits, k: int, *, use_kernel: bool = True, **kw):
    if not use_kernel:
        return topk_gating_ref(logits, k)
    return topk_gating(logits, k=k, **kw)
