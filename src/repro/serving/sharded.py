"""Tensor-parallel sharded serving: head-sharded paged pools and the
mesh-wide fused device step.

Partition the MEMORY, not the compute.  CAMformer's BA-CAM banks are
physically partitioned associative memories searched in parallel; the
serving analog shards the paged KV pool over a 1-axis ``("tp",)`` device
mesh (launch/mesh.py :func:`make_tp_mesh`) so each device holds a
kv-head slice of EVERY page and scores it locally:

  * every ``page_spec`` leaf whose logical axes name ``"kv_heads"``
    (dense ``k_pages``/``v_pages``, binary/camformer ``kp_pages``/
    ``k_scale``/``k_means``) gets one :class:`NamedSharding` placing
    ``"tp"`` on that axis — :func:`pool_partition_specs` derives the
    spec tree mechanically from the logical-axes tuples every backend
    already publishes, so new backends shard for free;
  * there is exactly ONE host page table and the host-pure ``Scheduler``
    is untouched — ``plan_tick()`` never reads device values, so the
    same plan drives a 1-device or an N-device step;
  * the whole tick — per-layer ``backend.paged_decode`` on local head
    slices, the paged cache write, and the vectorized keyed sampling —
    runs as ONE ``shard_map``-fused jitted step (:func:`shard_step`),
    with the sampled token ids still the only per-tick host readback.

Why all-gather of per-head attention outputs instead of a psum of
partial output projections: attention heads are independent, so gathering
the per-device head slices (models/attention.py) is pure concatenation —
no arithmetic — and every device reconstructs bit-identical full-head
activations.  The rest of the forward then runs replicated on each
device, producing identical logits and identical keyed samples, which is
what makes the tp>1 token streams bit-for-bit equal to the single-device
engine's.  A psum of per-shard partial ``wo`` projections would change
floating-point summation order and break token-for-token identity.

COW prefix forks and ``truncate_to`` rollback need no new code paths:
the fork copies pages along the PAGE axis (never the head axis), so the
same ``fork_page`` body runs shard_map-wrapped over the sharded
pools, and rollback is host page-table arithmetic only.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["HEAD_AXIS", "TP_AXIS", "leaf_partition_spec",
           "pool_partition_specs", "shard_pools", "replicate", "shard_step"]

HEAD_AXIS = "kv_heads"  # the logical axis every page pool shards over
TP_AXIS = "tp"  # the mesh axis name (see launch/mesh.py make_tp_mesh)


def leaf_partition_spec(axes: Sequence[Optional[str]]) -> P:
    """PartitionSpec for one page_spec leaf: ``"tp"`` on the kv-head
    axis, every other dimension replicated."""
    return P(*(TP_AXIS if a == HEAD_AXIS else None for a in axes))


def _leaf_spec(name: str, sds: jax.ShapeDtypeStruct,
               axes: Tuple[Optional[str], ...], tp: int) -> P:
    if HEAD_AXIS not in axes:
        return P()
    dim = axes.index(HEAD_AXIS)
    if sds.shape[dim] % tp != 0:
        raise ValueError(
            f"page_spec leaf {name!r}: kv-head axis has extent "
            f"{sds.shape[dim]} (axis {dim} of shape {sds.shape}), which "
            f"does not divide over tp={tp}; pick a tp degree that divides "
            "n_kv_heads")
    return leaf_partition_spec(axes)


def pool_partition_specs(specs, tp: int):
    """Derive the PartitionSpec pytree for a page-pool tree from the
    ``(ShapeDtypeStruct, logical_axes)`` leaves of ``md.page_specs``.

    Mirrors the pool tree's structure exactly (uniform stacks: one dict
    with a leading "layers" axis; mixed ``layer_backends`` policies: a
    tuple of per-layer dicts) so the result drops straight into
    shard_map ``in_specs``/``out_specs`` and :func:`shard_pools`.
    Raises ``ValueError`` naming the offending leaf when any kv-head
    axis does not divide by ``tp``.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")

    def one(layer, prefix=""):
        return {name: _leaf_spec(prefix + name, sds, axes, tp)
                for name, (sds, axes) in layer.items()}

    if isinstance(specs, tuple):  # mixed stack: per-layer trees
        return tuple(one(layer, f"layer{i}.")
                     for i, layer in enumerate(specs))
    return one(specs)


def shard_pools(pools, pspecs, mesh):
    """Place every pool leaf onto its head-sharded NamedSharding (the
    one-NamedSharding-per-page_spec-leaf allocation contract)."""

    def one(layer, layer_specs):
        return {k: jax.device_put(v, NamedSharding(mesh, layer_specs[k]))
                for k, v in layer.items()}

    if isinstance(pools, tuple):
        return tuple(one(lp, ls) for lp, ls in zip(pools, pspecs))
    return one(pools, pspecs)


def replicate(tree, mesh):
    """Replicate a pytree (params, token buffers) over the tp mesh so
    the fused step's non-pool inputs are already resident everywhere."""
    s = NamedSharding(mesh, P())
    return jax.tree.map(lambda a: jax.device_put(a, s), tree)


def shard_step(fn, mesh, in_specs, out_specs):
    """shard_map a fused engine step over the tp mesh.

    ``check_vma=False``: the varying-axes check cannot see that the
    ``all_gather`` in models/attention.py makes the attention output
    replicated again, and the layer scan then refuses a carry it types
    as varying.  The outputs ARE replicated — the gather reconstructs
    identical full-head activations on every device, which the identity
    tests assert.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
