"""A weights module for tests only (not a benchmark configuration): a
decoder LM whose feed-forward is the program's routed experts (the
``moe`` family: ``router (d, E)``, ``w_gate`` and ``w_up (E, d, f)``,
``w_down (E, f, d)``), so that a tree other than the dense one goes
through the harness's weights hook.  It defines what every module under
``benchmarks/chip/weights/`` defines (harness/cell.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import cell
from harness.counts import padded_vocab

# the seed's key and the embedding, head and final norm are the dense
# module's: only the blocks differ
_DENSE = cell.weights("dense_lm")
root_key, top = _DENSE.root_key, _DENSE.top

_IDS = {"ln1": 4, "ln2": 5, "wq": 6, "wk": 7, "wv": 8, "wo": 9,
        "router": 16, "w_gate": 17, "w_up": 18, "w_down": 19}
_ATTN = ("wq", "wk", "wv", "wo")
_FFN = ("router", "w_gate", "w_up", "w_down")


def matmul_weights_per_token(m: dict) -> int:
    """q/k/v/o, the router, the ``experts_per_token`` experts a token is
    routed to (not all of them) and the output head."""
    d, q, kv = m["d_model"], m["n_heads"] * m["head_dim"], (
        m["n_kv_heads"] * m["head_dim"])
    attn = 2 * d * q + 2 * d * kv
    ffn = d * m["n_experts"] + m["experts_per_token"] * 3 * d * m["d_ff"]
    return m["n_layers"] * (attn + ffn) + d * padded_vocab(m)


def _shapes(m: dict) -> dict:
    d, f, e = m["d_model"], m["d_ff"], m["n_experts"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return {"ln1": ((d,), None), "ln2": ((d,), None),
            "wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
            "wo": ((q, d), q), "router": ((d, e), d),
            "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
            "w_down": ((e, f, d), f)}


def _leaf(key, name, shape, fan_in, dtype):
    x = jax.random.normal(jax.random.fold_in(key, _IDS[name]), shape)
    x = 1.0 + 0.1 * x if fan_in is None else x * fan_in ** -0.5
    return x.astype(dtype)


def layer(key, m: dict, l, dtype=jnp.float32) -> dict:
    return {name: _leaf(jax.random.fold_in(key, l), name, shape, fan, dtype)
            for name, (shape, fan) in _shapes(m).items()}


def _tree(m, key):
    dtype = jnp.dtype(m["dtype"])
    stack = jax.vmap(lambda l: layer(key, m, l, dtype))(
        jnp.arange(m["n_layers"], dtype=jnp.uint32))
    t = top(key, m, dtype)
    return {"embed": {"tok": t["tok"], "head": t["head"]},
            "blocks": {"ln1": {"scale": stack["ln1"]},
                       "attn": {k: stack[k] for k in _ATTN},
                       "ln2": {"scale": stack["ln2"]},
                       "ffn": {k: stack[k] for k in _FFN}},
            "ln_f": {"scale": t["ln_f"]}}


def program_params(m: dict, seed: int):
    return jax.jit(lambda k: _tree(m, k))(root_key(seed))
