"""Jit'd public wrapper for the load-histogram kernel."""

from repro.kernels.histogram.kernel import load_histogram
from repro.kernels.histogram.ref import load_histogram_ref


def histogram(ids, num_dest: int, *, use_kernel: bool = True, **kw):
    if not use_kernel:
        return load_histogram_ref(ids, num_dest)
    return load_histogram(ids, num_dest=num_dest, **kw)
