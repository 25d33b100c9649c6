"""Logical-axis sharding rules with divisibility-checked fallback chains.

Every parameter / activation / cache tensor carries logical axis names
(models/module.py).  A rule maps a logical axis to an ordered list of mesh
axis candidates (each a mesh-axis name or tuple of names).  Resolution walks
each tensor dimension in order, assigns the first candidate whose mesh size
divides the dimension and whose mesh axes are still free — so e.g. GQA KV
caches with 8 heads on a 16-way `model` axis automatically fall through to
sequence (context) parallelism, and batch=1 long-context decode gives its
axes to the KV sequence dimension.  This is what makes all 40 assigned
(arch x shape) cells resolve without per-cell hand-written specs.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "PARAM_RULES",
    "ACT_RULES",
    "CACHE_RULES",
    "resolve_spec",
    "tree_pspecs",
    "tree_shardings",
    "constrain",
    "set_parallelism_profile",
]

# Candidates may reference axes absent from the current mesh ("pod" on the
# single-pod mesh); absent axes are skipped.
PARAM_RULES = {
    "embed": [("pod", "data"), ("data",)],  # FSDP (ZeRO-3 style)
    "mlp": [("model",)],
    "heads": [("model",)],  # fused n_heads*head_dim projection dim
    "kv_heads": [("model",)],  # fused n_kv*head_dim (divisible even when
    #                            the raw head count is not)
    "vocab": [("model",)],
    "experts": [("model",)],
    "expert_mlp": [("model",)],
    "rnn": [("model",)],
    "layers": [],  # scan axis: never sharded
    "conv": [],
    "head_dim": [],
}

# Serving weights: TP over `model` only, replicated across data (each data
# column serves its own requests) — FSDP gathers would re-stream the full
# weights over ICI every decode step.
SERVE_PARAM_RULES = {
    **{k: v for k, v in PARAM_RULES.items()},
    "embed": [],
}

ACT_RULES = {
    "batch": [("pod", "data"), ("data",), ("pod",)],
    "seq": [],  # sequence kept unsharded in-layer for train/prefill
    # Residual-stream sequence between blocks (Megatron sequence
    # parallelism): layer inputs/outputs + activation checkpoints are
    # seq-sharded over `model`; GSPMD turns the block-boundary TP
    # all-reduces into equal-volume all-gather/reduce-scatter pairs and the
    # per-layer saved activations shrink by the model-axis size.  Recurrent
    # families (rwkv/rglru time scans) do NOT use this axis.
    "res_seq": [("model",)],
    # Attention-interior query sequence: takes `model` ONLY when the head
    # axis could not (heads % model != 0, e.g. yi-34b 56H, granite 24H,
    # recurrentgemma 10H) => sequence parallelism inside attention instead
    # of a partially-sharded contraction that all-reduces the score tensor.
    "att_q_seq": [("model",)],
    "embed": [],
    "heads": [("model",)],
    "kv_heads": [("model",)],
    "mlp": [("model",)],
    "vocab": [("model",)],
    "experts": [("model",)],
    "capacity": [("model",)],
    "expert_mlp": [("model",)],
    "rnn": [("model",)],
    "head_dim": [],
}

# KV caches / recurrent states: when batch or heads cannot take the mesh
# axes, the cache sequence dim picks them up => context parallelism.
CACHE_RULES = {
    "batch": [("pod", "data"), ("data",), ("pod",)],
    "kv_heads": [("model",)],
    "heads": [("model",)],
    "kv_seq": [("pod", "data", "model"), ("data", "model"), ("pod", "data"),
               ("data",), ("model",)],
    "head_dim": [],
    "embed": [],
    "rnn": [("model",)],
    "conv": [],
}


_PROFILE = "tp"


def set_parallelism_profile(name: str):
    """Switch the global sharding profile.

    tp (default): Megatron-style — params/activations tensor-sharded over
        `model`, FSDP over `data`, batch over (`pod`,`data`).
    dp: pure data-parallel + ZeRO-3 — batch shards over EVERY axis
        ((`pod`,`data`,`model`)) and params FSDP over the same; because the
        batch/embed dims resolve FIRST and the divisibility resolver skips
        taken axes, every downstream rule (heads/mlp/experts/res_seq/...)
        degrades to local automatically.  This wins whenever per-device
        tokens are small relative to weight reuse (see EXPERIMENTS §Perf:
        granite-3B and qwen-110B train cells).
    """
    global _PROFILE
    if name not in ("tp", "dp"):
        raise ValueError(name)
    _PROFILE = name
    all_axes = [("pod", "data", "model"), ("data", "model")]
    if name == "dp":
        PARAM_RULES["embed"] = list(all_axes) + [("data",)]
        ACT_RULES["batch"] = list(all_axes) + [("data",), ("model",)]
        CACHE_RULES["batch"] = list(all_axes) + [("data",), ("model",)]
    else:
        PARAM_RULES["embed"] = [("pod", "data"), ("data",)]
        ACT_RULES["batch"] = [("pod", "data"), ("data",), ("pod",)]
        CACHE_RULES["batch"] = [("pod", "data"), ("data",), ("pod",)]


def get_parallelism_profile() -> str:
    return _PROFILE


def _mesh_size(mesh: Mesh, axes: Sequence[str]) -> Optional[int]:
    try:
        return math.prod(mesh.shape[a] for a in axes)
    except KeyError:
        return None  # candidate references an axis absent from this mesh


def resolve_spec(
    logical_axes: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: dict,
) -> P:
    """Resolve logical axes -> PartitionSpec under divisibility fallback."""
    taken: set = set()
    entries = []
    for name, dim in zip(logical_axes, shape):
        assigned = None
        for cand in rules.get(name, ()) if name else ():
            cand_t = (cand,) if isinstance(cand, str) else tuple(cand)
            # drop absent axes from the candidate rather than skipping it
            cand_t = tuple(a for a in cand_t if a in mesh.shape)
            if not cand_t or any(a in taken for a in cand_t):
                continue
            size = _mesh_size(mesh, cand_t)
            if size and dim % size == 0 and dim > 0:
                assigned = cand_t
                taken.update(cand_t)
                break
        if assigned is None:
            entries.append(None)
        elif len(assigned) == 1:
            entries.append(assigned[0])
        else:
            entries.append(assigned)
    return P(*entries)


def tree_pspecs(axes_tree, shapes_tree, mesh: Mesh, rules: dict):
    """Zip an axes tree with a shapes tree into PartitionSpecs."""
    return jax.tree.map(
        lambda ax, sh: resolve_spec(ax, sh.shape, mesh, rules),
        axes_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )


def tree_shardings(axes_tree, shapes_tree, mesh: Mesh, rules: dict):
    specs = tree_pspecs(axes_tree, shapes_tree, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]],
              rules: dict = ACT_RULES) -> jax.Array:
    """with_sharding_constraint from logical axes.

    No-op unless an ambient mesh is installed (`jax.set_mesh(mesh)` — done
    by the dry-run / trainer / server launchers); models stay mesh-agnostic.
    """
    env = jax.sharding.get_abstract_mesh()
    if env is None or not env.shape:  # no mesh context
        return x
    spec = resolve_spec(logical_axes, x.shape, env, rules)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)
