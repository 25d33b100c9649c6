"""Kernels: CAM decode kernel's share of its roofline, in %.  The kernel
is bound by memory: the least time is the bytes it needs (packed keys
of the live pages, query rows, stage-1 candidates; harness/counts.py)
over peak HBM bandwidth.  Bytes are counted from the kv_len of every
decode row that produced a token in the span, once per layer."""
from harness import counts
from harness.kernels import CAM_KERNEL


def read(w):
    if w.trace is None:
        return None
    t = w.trace.kernel_s(CAM_KERNEL)
    if not t:
        return None
    per_layer = counts.cam_kernel_bytes(w.model, w.engine["page_size"],
                                        w.decode_kv_lens())
    need = per_layer * w.model["n_layers"]
    if not need:
        return None
    return 100.0 * need / w.peaks["hbm_bytes_per_s"] / t
