"""Pallas TPU kernel: fused BA-CAM scoring + stage-1 hierarchical top-k.

This fuses the paper's *Association* stage exactly as the hardware pipelines
it (Sec. III-B1): while the BA-CAM scans key tiles, a bitonic top-2 keeps the
best `stage1_k` scores per tile of `group_size`(=CAM_H=16) keys, and ONLY the
candidates leave the stage.  On TPU the same fusion is a memory-traffic
optimization: the (R, Skv) score matrix never reaches HBM — per key-group
only `stage1_k` (value, index) pairs are written, an 8x/16x reduction in
score traffic (2*16/4 bytes per 16 keys vs 64 bytes).

Masking (causal / sliding window / valid-cache-length) is applied in-kernel
from query positions, so the kernel also serves decode (R=1 row per query)
against a partially-filled cache.

VMEM (defaults bq=256, bk=512, W<=8): scores acc 512 KiB + operands ~24 KiB
+ candidate blocks (256 x 64 x 4 B x 2) 128 KiB  =>  < 1 MiB of 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import MASKED_SCORE


def score_and_stage1(q_words, k_words, ok, *, d, group, stage1_k,
                     base_offset):
    """Shared kernel-body block: BA-CAM scoring + stage-1 top-k.

    Used by both the contiguous (this module) and the paged
    (bacam_decode.py) association kernels so the tie-breaking and masking
    semantics can never diverge.

    q_words: (R, W) uint32; k_words: (S, W) uint32; ok: (R, S) bool
    validity mask (the caller's mask source is the only difference
    between the kernels); base_offset: global index of k_words[0].

    Returns (vals, idx): (R, S/group * stage1_k) int32 — per group the
    top stage1_k masked scores (MASKED_SCORE when invalid) and their
    global key indices, group-major / top-k-minor.
    """
    rows, words = q_words.shape
    bk = k_words.shape[0]

    # --- BA-CAM scoring (see bacam_mvm.py) ---
    acc = jnp.zeros((rows, bk), jnp.int32)
    for w in range(words):  # static unroll: words = d/32
        x = jnp.bitwise_xor(q_words[:, w][:, None], k_words[:, w][None, :])
        acc = acc + jax.lax.population_count(x).astype(jnp.int32)
    scores = jnp.where(ok, jnp.int32(d) - 2 * acc, MASKED_SCORE)

    # --- stage-1 top-k per group of `group` keys (bitonic top-2 dual) ---
    # Sequential max-extraction == stable top-k (lowest index first among
    # equal scores, as lax.top_k in the oracles).  Built from what Mosaic
    # lowers: float32 lane reductions (scores are integers with |s| <= d
    # and MASKED_SCORE = -2**30, so float32 holds every one exactly) and
    # iota selects; no argmax, no in-kernel reshape.  An extracted key is
    # knocked out to -inf, BELOW MASKED_SCORE, so a group with fewer than
    # stage1_k valid keys yields its masked keys in index order too.
    ngroups = bk // group
    ncand = ngroups * stage1_k
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
    lane_f = lane.astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, ncand), 1)
    cur = scores.astype(jnp.float32)
    vals = jnp.zeros((rows, ncand), jnp.int32)
    idxs = jnp.zeros((rows, ncand), jnp.int32)
    for g in range(ngroups):
        in_g = jnp.logical_and(lane >= g * group, lane < (g + 1) * group)
        for t in range(stage1_k):
            sg = jnp.where(in_g, cur, -jnp.inf)
            m = sg.max(axis=-1, keepdims=True)  # (R, 1)
            am = jnp.where(sg == m, lane_f, jnp.float32(bk)).min(
                axis=-1, keepdims=True)  # lowest lane holding the max
            here = col == g * stage1_k + t  # group-major / top-k-minor
            vals = jnp.where(here, m.astype(jnp.int32), vals)
            idxs = jnp.where(here, am.astype(jnp.int32) + base_offset, idxs)
            cur = jnp.where(lane_f == am, -jnp.inf, cur)
    return vals, idxs


def _kernel(
    q_ref,
    k_ref,
    pos_ref,
    kvlen_ref,
    vals_ref,
    idx_ref,
    *,
    d: int,
    group: int,
    stage1_k: int,
    block_k: int,
    causal: bool,
    window: int | None,
):
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    j = pl.program_id(2)

    # --- masking from positions (matchline "search enable" in hardware) ---
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    qpos = pos_ref[0][:, None]
    ok = kpos < kvlen_ref[0, 0]
    if causal:
        ok = jnp.logical_and(ok, kpos <= qpos)
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)

    vals_ref[0], idx_ref[0] = score_and_stage1(
        q_ref[0], k_ref[0], ok, d=d, group=group, stage1_k=stage1_k,
        base_offset=j * block_k)


@functools.partial(
    jax.jit,
    static_argnames=(
        "d", "group", "stage1_k", "causal", "window", "block_q", "block_k", "interpret",
    ),
)
def bacam_topk_stage1(
    q_packed: jax.Array,
    k_packed: jax.Array,
    q_pos: jax.Array,
    kv_len: jax.Array,
    *,
    d: int,
    group: int = 16,
    stage1_k: int = 2,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool,
):
    """Fused binary scores + stage-1 top-k.

    Args:
      q_packed: (B, R, W) uint32;  k_packed: (B, Skv, W) uint32.
      q_pos: (B, R) int32 query positions (masking); kv_len: (B, 1) int32
        number of valid keys (rest of the padded cache is masked).

    Returns:
      (cand_vals, cand_idx): (B, R, stage1_k*Skv/group) int32; masked
      candidates hold MASKED_SCORE.  Group-major, top-k-minor order
      (matches ref.bacam_topk_stage1_ref).
    """
    b, r, words = q_packed.shape
    skv = k_packed.shape[1]
    assert words * 32 == d
    assert r % block_q == 0 and skv % block_k == 0 and block_k % group == 0
    grid = (b, r // block_q, skv // block_k)
    ncand_blk = stage1_k * (block_k // group)
    ncand = stage1_k * (skv // group)
    kern = functools.partial(
        _kernel,
        d=d, group=group, stage1_k=stage1_k,
        block_k=block_k, causal=causal, window=window,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, words), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_k, words), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, block_q), lambda b_, i, j: (b_, i)),
            pl.BlockSpec((1, 1), lambda b_, i, j: (b_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, ncand_blk), lambda b_, i, j: (b_, i, j)),
            pl.BlockSpec((1, block_q, ncand_blk), lambda b_, i, j: (b_, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, r, ncand), jnp.int32),
            jax.ShapeDtypeStruct((b, r, ncand), jnp.int32),
        ],
        interpret=interpret,
    )(q_packed, k_packed, q_pos, kv_len)
