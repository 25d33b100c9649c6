"""Operations, bytes and chip peaks: the yardstick the per-layer metrics
divide by.  Everything here is computed from the configuration's sizes,
never read from the program.

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


def matmul_weights_per_token(model: dict) -> int:
    """Weights of the matrix multiplications one token passes through:
    q/k/v/o projections, the gated feed-forward and the output head (over
    the padded vocabulary the program multiplies).  Embedding lookups and
    attention arithmetic are left out, so every attention backend counts
    the same."""
    d, hd = model["d_model"], model["head_dim"]
    q_dim = model["n_heads"] * hd
    kv_dim = model["n_kv_heads"] * hd
    attn = d * q_dim + 2 * d * kv_dim + q_dim * d
    ffn = 3 * d * model["d_ff"]
    return model["n_layers"] * (attn + ffn) + d * padded_vocab(model)


def matmul_flops_per_token(model: dict) -> int:
    return 2 * matmul_weights_per_token(model)


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
