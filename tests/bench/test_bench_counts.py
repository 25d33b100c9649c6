"""The yardstick: matmul operations per token, the CAM kernel's bytes,
and the table of chip peaks."""

import json
import os

import pytest

from harness import cell as C
from harness import counts


def _model(name):
    with open(os.path.join(C.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


BERT = "camformer-bert-f32"
QWEN = {"n_layers": 8, "d_model": 4096, "n_heads": 32, "n_kv_heads": 4,
        "head_dim": 128, "d_ff": 13440, "vocab": 92416}


@pytest.mark.parametrize("model,weights", [
    # 4 x (4 x 1024^2 + 3 x 1024 x 4096) + 1024 x 30592 (padded vocab)
    ({}, 98_435_072),
    # the whole model, 24 layers: 434 M
    ({"n_layers": 24}, 433_979_392),
    # CodeQwen1.5-7B widths, 8 layers, 4 kv heads:
    # 8 x (2 x 4096^2 + 2 x 4096 x 512 + 3 x 4096 x 13440) + 4096 x 92416
    (QWEN, 2_001_731_584),
])
def test_matmul_flops_per_token(model, weights):
    m = dict(_model(BERT), **model)
    assert counts.matmul_weights_per_token(m) == weights
    assert counts.matmul_flops_per_token(m) == 2 * weights


def test_cam_kernel_bytes_count_live_pages_only():
    m = _model(BERT)
    # live pages: 0 (inert) + 1 + 1 + 2 + 16 = 20; 16 kv heads, 2 words
    keys = 20 * 64 * 16 * 2 * 4
    query = 4 * 16 * 1 * 2 * 4
    cands = 20 * 8 * 16 * 1 * 2 * 4  # 8 candidates a page, value + index
    assert counts.cam_kernel_bytes(m, 64, [0, 1, 64, 65, 1024]) == (
        keys + query + cands)
    assert counts.cam_kernel_bytes(m, 64, [0, 0]) == 0


def test_peaks_of_a_v5e_and_an_unknown_device():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")
