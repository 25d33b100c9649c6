"""Weights modules (benchmarks/chip/weights/<name>.py): the dense maker
makes, bit for bit, the weights it made before it became a module of its
own, and a module's tree is the program's parameter tree, for every
configuration in BENCHMARK.json and for a tree that is not dense."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import moe_lm
import tiny
from harness import cell as C

SEED = 3000000501


def _model(name):
    with open(os.path.join(C.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def _tiny(**over):
    return dict(BERT, **dict(tiny.MODEL, **over))


BERT = _model("camformer-bert-f32")
# the program's ``moe`` family at test sizes: 4 experts, 2 a token
MOE = _tiny(family="moe", n_experts=4, experts_per_token=2, d_ff=32)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# Digests of the weights the dense maker made before it moved to
# weights/dense_lm.py (recorded on the CPU from the maker as it was).
DIGESTS = {
    "tiny": "d1de57ce43047353a24005f2f4418e92c8a24d214ca525195d1fefe2540c7d0b",
    "tiny_gqa_bias_bf16":
        "18eedc3bf14cf609793a416fd001bbf54fa2b372d169608c050f06348ff9baf0",
    "bert_layer0":
        "bceed450465b3e04a58cd7c721c9c9205c90a58b91684f59bcf47243124499f9",
    "bert_top":
        "39648188d1c7d3629cd51ded5319571a1aa6595bd63c0b9a72408ea1ce3fd437",
}
MAKE = {
    # every leaf of program_params, tiny sizes (tests/bench/tiny.py)
    "tiny": lambda W: W.program_params(_tiny(), 2**33 + 7),
    # the q/k/v biases, grouped-query widths and the bf16 type too
    "tiny_gqa_bias_bf16": lambda W: W.program_params(
        _tiny(n_kv_heads=1, qkv_bias=True, dtype="bfloat16"), 3),
    # one layer and the embedding and head at camformer-bert's widths, as
    # the reference makes them
    "bert_layer0": lambda W: W.layer(W.root_key(SEED), BERT, 0, jnp.float32),
    "bert_top": lambda W: W.top(W.root_key(SEED), BERT, jnp.float32),
}


@pytest.mark.parametrize("case", sorted(DIGESTS))
def test_dense_lm_makes_the_weights_it_made_before(case):
    assert _digest(MAKE[case](C.weights("dense_lm"))) == DIGESTS[case]


def assert_the_programs_tree(W, model):
    """``W.program_params(model, seed)`` has the tree structure, shapes and
    dtypes of the program's parameter specs for ``model``, served in the
    model's ``dtype`` (compared by shape only: nothing is made)."""
    from repro.configs.base import ModelConfig
    from repro.models import get_model_def
    from repro.models.module import param_shapes

    cfg = ModelConfig(**model)
    want = param_shapes(get_model_def(cfg).specs(cfg),
                        jnp.dtype(model["dtype"]))
    got = jax.eval_shape(lambda: W.program_params(model, 2**33 + 1))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    shapes = lambda t: [(x.shape, x.dtype) for x in jax.tree.leaves(t)]
    assert shapes(got) == shapes(want)


@pytest.mark.parametrize("config", [c["name"] for c in
                                    C.load_benchmark()["configs"]])
def test_every_configurations_weights_are_the_programs_tree(config):
    with open(os.path.join(C.HERE, "configs", f"{config}.json")) as f:
        conf = json.load(f)
    assert_the_programs_tree(C.weights(conf["weights"]), conf["model"])


def test_a_tree_with_experts_is_the_programs_moe_tree():
    assert_the_programs_tree(moe_lm, MOE)
    t = moe_lm.program_params(MOE, 5)
    assert t["blocks"]["ffn"]["w_gate"].shape == (2, 4, 64, 32)
    # the router, and 2 of the 4 experts a token
    d, v = 64, 256
    assert moe_lm.matmul_weights_per_token(MOE) == (
        2 * (4 * d * d + d * 4 + 2 * 3 * d * 32) + d * v)
