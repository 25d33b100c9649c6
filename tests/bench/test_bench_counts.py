"""The yardstick: matmul weights per token (of the dense weights module),
the CAM kernel's bytes, and the table of chip peaks."""

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
    assert C.weights("dense_lm").matmul_weights_per_token(m) == weights


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


def _counted(before, after, *, weights=0, tokens=0, seconds=1.0):
    """A window of hand-made engine counters and ``tokens`` delivered."""
    from types import SimpleNamespace

    from harness.window import Window

    rec = SimpleNamespace(times=[seconds / 2] * tokens)
    return Window(t0=0.0, t1=seconds, records=[rec], before=before,
                  after=after, model={},
                  engine={"max_batch": 64, "prefill_slice": 256},
                  device_kind="TPU v5 lite",
                  matmul_weights_per_token=weights)


def test_prefill_pad_share_divides_by_the_rows_the_step_computes():
    read = C.reader("prefill_pad_share.decode")
    # 30 one-row prefill steps of 256 positions carried 4900 prompt tokens
    w = _counted({"prefill_tokens": 100, "prefill_ticks": 5,
                  "prefill_rows": 10},
                 {"prefill_tokens": 5000, "prefill_ticks": 35,
                  "prefill_rows": 40})
    assert read(w) == pytest.approx(1 - 4900 / (30 * 256))
    # no prefill in the window; an engine without the counter
    assert read(_counted({"prefill_tokens": 0, "prefill_rows": 4},
                         {"prefill_tokens": 0, "prefill_rows": 4})) is None
    assert read(_counted({"prefill_tokens": 0, "prefill_ticks": 0},
                         {"prefill_tokens": 90, "prefill_ticks": 1})) is None


def test_step_mfu_counts_with_the_weights_modules_count():
    w = _counted({"prefill_tokens": 0}, {"prefill_tokens": 500},
                 weights=1_000_000, tokens=1500, seconds=2.0)
    assert C.reader("step_mfu.decode")(w) == pytest.approx(
        100 * 2000 * 2 * 1_000_000 / 2.0 / 197e12)


def test_counters_skip_a_counter_the_engine_lacks():
    from types import SimpleNamespace

    from harness.window import counters

    engine = SimpleNamespace(ticks=3, prefill_tokens=5, prefill_ticks=1)
    assert counters(engine) == {"ticks": 3, "prefill_tokens": 5,
                                "prefill_ticks": 1}
    engine.prefill_rows = 2
    assert counters(engine)["prefill_rows"] == 2
