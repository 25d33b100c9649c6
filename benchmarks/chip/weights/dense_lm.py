"""Weights of a dense decoder LM (the program's ``dense`` family), made
from ``--seed`` by the benchmark itself, on the device, in one jitted
call, in the type they are served in (the model's ``dtype``).

Each leaf of layer ``l`` is ``normal(fold_in(fold_in(key, l), leaf))``
times its scale, rounded to bf16 (and held in the served type), so one
layer's weights can be made again on their own (``layer``): that is how
the plain reference gets the same numbers without taking anything the
program holds.  The tree has the layout the serving program takes as its
parameters.  Every weights module under ``weights/`` defines the five
public names of this one (harness/cell.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.counts import padded_vocab

# leaf -> fold-in id; never renumber (weights of every seed would change)
_IDS = {"tok": 1, "head": 2, "ln_f": 3, "ln1": 4, "ln2": 5, "wq": 6,
        "wk": 7, "wv": 8, "wo": 9, "bq": 10, "bk": 11, "bv": 12,
        "w_gate": 13, "w_up": 14, "w_down": 15}
NORM_SPREAD = 0.1  # norm scales are 1 + 0.1 * normal
BIAS_STD = 0.05


def root_key(seed: int) -> jax.Array:
    word = np.random.SeedSequence(seed).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word))


def matmul_weights_per_token(m: dict) -> int:
    """Weights of the matrix multiplications one token passes through:
    q/k/v/o projections, the gated feed-forward and the output head (over
    the padded vocabulary the program multiplies).  Embedding lookups and
    attention arithmetic are left out, so every attention backend counts
    the same."""
    d, hd = m["d_model"], m["head_dim"]
    q_dim = m["n_heads"] * hd
    kv_dim = m["n_kv_heads"] * hd
    attn = d * q_dim + 2 * d * kv_dim + q_dim * d
    ffn = 3 * d * m["d_ff"]
    return m["n_layers"] * (attn + ffn) + d * padded_vocab(m)


def _shapes(m: dict) -> dict:
    d, hd = m["d_model"], m["head_dim"]
    qd, kvd = m["n_heads"] * hd, m["n_kv_heads"] * hd
    s = {"ln1": ((d,), None), "ln2": ((d,), None),
         "wq": ((d, qd), d), "wk": ((d, kvd), d), "wv": ((d, kvd), d),
         "wo": ((qd, d), qd), "w_gate": ((d, m["d_ff"]), d),
         "w_up": ((d, m["d_ff"]), d), "w_down": ((m["d_ff"], d), m["d_ff"])}
    if m.get("qkv_bias"):
        s.update(bq=((qd,), 0), bk=((kvd,), 0), bv=((kvd,), 0))
    return s


def _leaf(key, name, shape, fan_in, dtype):
    x = jax.random.normal(jax.random.fold_in(key, _IDS[name]), shape,
                          jnp.float32)
    if fan_in is None:  # norm scale
        x = 1.0 + NORM_SPREAD * x
    elif fan_in == 0:  # bias
        x = BIAS_STD * x
    else:
        x = x * fan_in ** -0.5
    return x.astype(jnp.bfloat16).astype(dtype)


def layer(key, m: dict, l, dtype=jnp.bfloat16) -> dict:
    """Layer ``l``'s leaves, flat: {"wq": ..., "ln1": ..., ...}."""
    return {name: _leaf(jax.random.fold_in(key, l), name, shape, fan, dtype)
            for name, (shape, fan) in _shapes(m).items()}


def top(key, m: dict, dtype=jnp.bfloat16) -> dict:
    """Embedding, output head and final norm scale."""
    v, d = padded_vocab(m), m["d_model"]
    tok = _leaf(key, "tok", (v, d), 1, dtype)
    head = _leaf(key, "head", (d, v), d, dtype)
    return {"tok": tok, "head": head,
            "ln_f": _leaf(key, "ln_f", (d,), None, dtype)}


def _tree(m, key):
    dtype = jnp.dtype(m.get("dtype", "bfloat16"))
    stack = jax.vmap(lambda l: layer(key, m, l, dtype))(
        jnp.arange(m["n_layers"], dtype=jnp.uint32))
    t = top(key, m, dtype)
    attn = {k: stack[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in stack}
    return {
        "embed": {"tok": t["tok"], "head": t["head"]},
        "blocks": {"ln1": {"scale": stack["ln1"]}, "attn": attn,
                   "ln2": {"scale": stack["ln2"]},
                   "ffn": {k: stack[k] for k in ("w_gate", "w_up",
                                                  "w_down")}},
        "ln_f": {"scale": t["ln_f"]},
    }


def program_params(m: dict, seed: int):
    """The whole tree, made on the device in one jitted call."""
    return jax.jit(lambda k: _tree(m, k))(root_key(seed))
