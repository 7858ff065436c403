"""Jit'd public wrapper for the dispatch-gather kernel."""

from repro.kernels.dispatch.kernel import dispatch_gather
from repro.kernels.dispatch.ref import dispatch_gather_ref


def dispatch(x, src, valid, *, use_kernel: bool = True, **kw):
    """Routing-plan gather: the compiled kernel (``interpret=True`` in
    ``kw`` runs it in the Pallas interpreter) or the jnp reference."""
    if not use_kernel:
        return dispatch_gather_ref(x, src, valid)
    return dispatch_gather(x, src, valid, **kw)
