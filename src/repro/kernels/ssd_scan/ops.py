"""Jit'd public wrapper for the SSD state scan."""

from repro.kernels.ssd_scan.kernel import ssd_state_scan
from repro.kernels.ssd_scan.ref import ssd_state_scan_ref


def state_scan(states, decay, *, use_kernel: bool = True, **kw):
    if not use_kernel:
        return ssd_state_scan_ref(states, decay)
    return ssd_state_scan(states, decay, **kw)
