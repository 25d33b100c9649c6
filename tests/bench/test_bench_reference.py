"""The plain reference against the program, at a size a CPU test holds.

A float32 program reads a logit gap of exactly 0: the reference computes
the same model (CAM selection, tie order, chunked key scale), so the
served greedy tokens are its own argmax.  A control in a lower precision,
put in the program's place, reads far above that."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import check
from harness import cell as C

# At this size and in float32 a sound run reads exactly 0 and the float8
# control and the planted faults read 1 or more (CPU runs); a cell's own
# limits come from readings on the chip at full size.
LIMIT = 0.1


@pytest.mark.parametrize("n_kv_heads", [2, 1])
def test_reference_matches_a_float32_program_and_the_control_fails(
        n_kv_heads, tmp_path):
    limit = LIMIT
    res = tiny.run("bert-cam.decode", tmp_path, control="float8",
                   limit=limit, n_kv_heads=n_kv_heads)
    chk = res["_check"]
    assert chk["requests"] == 2 and chk["tokens"] > 0, chk
    assert chk["logit_gap"] == 0.0, chk
    assert chk["mean_logit_gap"] == 0.0 and chk["off_argmax_share"] == 0.0
    assert chk["control_logit_gap"] > limit, chk
    assert res["correct"] is True, res["checks"]


def test_high_control_takes_every_product_at_high_precision():
    """The control of a float32 configuration at "highest" runs each
    matrix product, the attention's sum of values too, at "high" (three
    bf16 passes on the TPU), and the reference itself at "highest"."""
    ref = C.reference("camformer_lm")
    m = {"n_heads": 2, "n_kv_heads": 1, "head_dim": 8, "k_top": 4,
         "group_size": 4, "stage1_k": 2, "rope_theta": 1e4}
    d = 16
    rng = np.random.default_rng(0)
    w = {k: jnp.asarray(rng.standard_normal(s), jnp.float32) for k, s in
         (("ln1", d), ("ln2", d), ("wq", (d, 16)), ("wk", (d, 8)),
          ("wv", (d, 8)), ("wo", (16, d)), ("w_gate", (d, 24)),
          ("w_up", (d, 24)), ("w_down", (24, d)))}
    x = jnp.asarray(rng.standard_normal((1, 8, d)), jnp.float32)
    pos = jnp.arange(8)
    e_idx = jnp.asarray(ref.chunk_ends(8, 8, 8, 8))[None]

    def precisions(control):
        text = str(jax.make_jaxpr(lambda w, x: ref._layer(
            w, x, pos, e_idx, m, control))(w, x))
        return re.findall(r"precision=\(?(Precision\.\w+)", text)

    # eight float32 products (q, k, v, the sum of values, o, the gated
    # feed-forward's three); the exact +-1 score product of the CAM
    # search is bf16 and takes no precision
    assert precisions(None) == ["Precision.HIGHEST"] * 8
    assert precisions("high") == ["Precision.HIGH"] * 8


def test_readings_of_the_gaps():
    got = check.readings(np.array([0.0, 0.0, 0.5, 1.5], np.float32))
    assert got == {"logit_gap": 1.5, "mean_logit_gap": 0.5,
                   "off_argmax_share": 0.5}
