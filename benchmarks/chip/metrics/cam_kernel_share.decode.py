"""Kernels: device time of the CAM decode kernel
(kernels/bacam_decode.py) over device busy time, in the traced span."""
from harness.kernels import CAM_KERNEL


def read(w):
    if w.trace is None or not w.trace.busy_s:
        return None
    k = w.trace.kernel_s(CAM_KERNEL)
    return k / w.trace.busy_s if k else None
