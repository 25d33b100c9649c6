"""Pallas TPU kernel: fused paged CAM decode (scoring + stage-1 top-k).

Decode-time association against the serving engine's *paged*, bit-packed
KV cache (serving/kv_cache.py): keys live in fixed-size physical pages of
``(H_kv, page_size, d/32)`` uint32 words, and each sequence's logical order
is given by a page table.  The page table is a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``), so the grid walks *logical* pages and
the BlockSpec index_map dereferences ``page_table[b, j]`` to DMA the right
physical page — the classic paged-attention gather, but over 1-bit keys.

Per (slot, kv-head, logical page) grid cell the kernel fuses:

  * BA-CAM scoring: popcount(q ^ k) over packed words — the (R, Skv) score
    matrix never exists in HBM (R = GQA group size rows per kv head);
  * masking from the slot's kv length (matchline "search enable");
  * stage-1 hierarchical top-k per group of ``group``(=CAM_H=16) keys.

Only ``stage1_k * page_size/group`` (value, index) candidate pairs leave
each page; stage-2 top-k + softmax + sparse-V contextualization run on that
tiny candidate set (core/attention.camformer_paged_attention).

Inactive slots point every page-table entry at the reserved trash page 0;
their scores are fully masked by ``kv_len`` so the garbage never surfaces.

VMEM per cell (defaults page=64, W<=8, R<=8): q 256 B + k 2 KiB + scores
R*64*4 B ~ 2 KiB, plus the candidate blocks: each output block is
(R, 8, ncp) int32 (ncp = stage1_k * page/group candidates per page; eight
pages' rows), one (8, 128) tile of 4 KiB per query row once lane-padded,
so two outputs double-buffered take 16 KiB * R (128 KiB at R=8),
whatever max_len is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bacam_topk import score_and_stage1

# logical pages per output block: the block's sublane extent, which the
# (8, 128) tiling rule wants a multiple of 8
_PAGES_PER_BLOCK = 8


def _kernel(
    pt_ref,
    kvlen_ref,
    qpos_ref,
    q_ref,
    k_ref,
    vals_ref,
    idx_ref,
    *,
    d: int,
    group: int,
    stage1_k: int,
    page: int,
    window: int | None,
):
    b = pl.program_id(0)
    j = pl.program_id(2)  # logical page index
    rows = q_ref.shape[2]

    # --- masking: validity (kv length) + causality from the slot's query
    # position (matchline "search enable"; decode rows share one qpos) ---
    kvl = kvlen_ref[b]
    qpos = qpos_ref[b]
    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
    ok = jnp.logical_and(kpos < kvl, kpos <= qpos)
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)

    # scoring + stage-1 shared with the contiguous kernel (bacam_topk.py)
    vals, idx = score_and_stage1(
        q_ref[0, 0], k_ref[0, 0], ok, d=d, group=group, stage1_k=stage1_k,
        base_offset=j * page)
    # the (R, 8, ncp) output block holds eight consecutive pages; page j
    # fills sublane row j % 8 of each query row's candidate table
    sub = j % _PAGES_PER_BLOCK
    for r in range(rows):
        vals_ref[0, 0, r, pl.ds(sub, 1), :] = vals[r:r + 1]
        idx_ref[0, 0, r, pl.ds(sub, 1), :] = idx[r:r + 1]


@functools.partial(
    jax.jit,
    static_argnames=("d", "group", "stage1_k", "window", "interpret"),
)
def bacam_paged_topk_stage1(
    q_packed: jax.Array,
    kp_pages: jax.Array,
    page_table: jax.Array,
    kv_len: jax.Array,
    q_pos: jax.Array,
    *,
    d: int,
    group: int = 16,
    stage1_k: int = 2,
    window: int | None = None,
    interpret: bool,
):
    """Fused paged binary scoring + stage-1 top-k for decode rows.

    Args:
      q_packed: (B, H_kv, R, W) uint32 — R = GQA-group query rows per kv
        head, all at one position per slot (decode: kv_len - 1).
      kp_pages: (n_pages, H_kv, page_size, W) uint32 key pool (one layer).
      page_table: (B, NP) int32 — logical->physical page map; unallocated
        entries must hold a valid (trash) page index.
      kv_len: (B,) int32 valid tokens per slot.
      q_pos: (B,) int32 query position per slot (causal/window anchor).

    Returns:
      (cand_vals, cand_idx): (B, H_kv, R, stage1_k * NP*page/group) int32;
      masked candidates hold MASKED_SCORE.  Logical-page-major, group-major,
      top-k-minor order (matches ref.bacam_paged_topk_ref and the ordering
      of core.topk.two_stage_topk over a gathered contiguous cache).
    """
    b, hkv, rows, words = q_packed.shape
    n_pages, _, page, _ = kp_pages.shape
    np_ = page_table.shape[1]
    assert words * 32 == d
    assert page % group == 0
    ncp = stage1_k * (page // group)  # candidates per page
    pb = _PAGES_PER_BLOCK
    np_out = -(-np_ // pb) * pb  # page rows padded to whole blocks
    grid = (b, hkv, np_)
    kern = functools.partial(
        _kernel,
        d=d, group=group, stage1_k=stage1_k,
        page=page, window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_table, kv_len, q_pos
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rows, words),
                         lambda b_, h, j, pt, kvl, qp: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page, words),
                         lambda b_, h, j, pt, kvl, qp: (pt[b_, j], h, 0, 0)),
        ],
        # the block's last two dims are (8 pages, ncp): ncp equals the
        # array's extent and 8 is a multiple of 8, which Mosaic's (8, 128)
        # tiling rule accepts for a narrow candidate axis.  The block is
        # written back after every eighth page, so its size does not grow
        # with max_len.
        out_specs=[
            pl.BlockSpec((1, 1, rows, pb, ncp),
                         lambda b_, h, j, pt, kvl, qp: (b_, h, 0, j // pb, 0)),
            pl.BlockSpec((1, 1, rows, pb, ncp),
                         lambda b_, h, j, pt, kvl, qp: (b_, h, 0, j // pb, 0)),
        ],
    )
    vals, idx = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rows, np_out, ncp), jnp.int32),
            jax.ShapeDtypeStruct((b, hkv, rows, np_out, ncp), jnp.int32),
        ],
        interpret=interpret,
        name="bacam_paged_topk_stage1",
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      q_pos.astype(jnp.int32), q_packed, kp_pages)
    # drop the padding page rows (never written), then the
    # logical-page-major candidate order is a row-major reshape
    return (vals[:, :, :, :np_].reshape(b, hkv, rows, np_ * ncp),
            idx[:, :, :, :np_].reshape(b, hkv, rows, np_ * ncp))
