"""Bytes and chip peaks: the yardstick the per-layer metrics divide by.
Everything here is computed from the configuration's sizes, never read
from the program.  The matrix weights a token passes through depend on
the parameter tree, so each weights module counts them
(``weights/<name>.py`` ``matmul_weights_per_token``).

Peaks are per chip, keyed by ``device_kind`` as JAX reports it.  A device
that is not in the table is an error, not a default.
"""

from __future__ import annotations

import math

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB HBM per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def padded_vocab(model: dict) -> int:
    return -(-model["vocab"] // 128) * 128


def cam_kernel_bytes(model: dict, page_size: int, kv_lens) -> int:
    """Bytes one layer's CAM decode kernel needs for one decode row per
    entry of ``kv_lens``: the packed keys of the live pages, the packed
    query rows, and the stage-1 candidates (value and index, int32) of the
    live pages.  Pages past ``kv_len`` are not needed and not counted."""
    hkv, d = model["n_kv_heads"], model["head_dim"]
    rows = model["n_heads"] // hkv
    words = d // 32
    cand_per_page = model["stage1_k"] * (page_size // model["group_size"])
    total = 0
    for kv_len in kv_lens:
        if kv_len <= 0:
            continue
        pages = math.ceil(kv_len / page_size)
        keys = pages * page_size * hkv * words * 4
        query = hkv * rows * words * 4
        cands = pages * cand_per_page * hkv * rows * 2 * 4
        total += keys + query + cands
    return total
