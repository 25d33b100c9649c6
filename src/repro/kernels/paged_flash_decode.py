"""Pallas TPU kernel: fused paged flash-decode for dense & binary scoring.

The digital-contextualization half of the serving stack: decode-time
softmax attention against the engine's *paged* KV pools
(serving/kv_cache.py) without ever gathering a slot's pages into logical
order.  The page table is a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``) — exactly the structure of the CAM
decode kernel (bacam_decode.py) — so the grid walks *logical* pages and
the BlockSpec index_map dereferences ``page_table[b, j]`` to DMA the
right physical K/V page.  Per (slot, kv-head, logical page) grid cell
the kernel fuses:

  * a per-page score tile (R, page) — R = GQA group rows per kv head
    at ONE decode position, or R = G * Sq chunk rows when ``sq > 1``
    (chunked prefill / speculative verify) — via one MXU dot; the
    (R, S_log) score matrix never exists in HBM;
  * masking from the slot's kv length / query position (+ window); for
    ``sq > 1`` the causal anchor is PER ROW: row r = g * sq + s sits at
    position ``q_pos[b] + s`` (chunk positions are contiguous from the
    slot's ``offsets``), which yields the intra-chunk causal mask;
  * an online (streaming) softmax: running max / denominator / output
    accumulator live in VMEM scratch across the page sweep (the
    canonical flash pattern of kernels/flash_attention.py), so there is
    no logical-order K/V gather and no (B, H_kv, NP*page, D) scratch.

ONE kernel skeleton serves both registered softmax realizations
(core/backend.py):

  * ``dense``  — bf16/f32 q·k scores (queries arrive pre-scaled by
    1/sqrt(d));
  * ``binary`` — HAD sign-match scoring (``binary=True``): the K tile is
    binarized in-register with ``core/binarize.sign_pm1`` semantics
    (x > 0 -> +1 else -1) and queries arrive as ±1 rows pre-scaled by
    the HAD softmax temperature (q_scale * running k_scale * 1/sqrt(d)),
    which is per-row and therefore folds into the query operand — the
    stream never needs a post-hoc rescale.

Rows with ``kv_len == 0`` are the fused-step contract's INERT rows:
every score masks away, the denominator stays zero, and the output is
a defined all-zeros vector that the engine never reads.  Inactive
page-table entries point at the reserved trash page 0; kv_len masking
keeps its garbage out of every live row's softmax.

``interpret`` has no default: ``False`` compiles the kernel for the TPU,
``True`` runs it through the Pallas interpreter (CPU debugging — same
semantics).  Off-TPU the ops wrapper runs the jnp page walk
(ref.paged_flash_decode_ref) instead of either.

VMEM per cell (defaults page=64, D<=256, R<=8): k/v tiles
2*64*256*4 B = 128 KiB + q/acc ~ 2*8*256*4 B ~ 16 KiB  =>  resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.topk import NEG_INF


def _kernel(
    pt_ref,
    kvlen_ref,
    qpos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    page: int,
    sq: int,
    binary: bool,
    window: int | None,
):
    b = pl.program_id(0)
    j = pl.program_id(2)  # logical page index
    nj = pl.num_programs(2)
    rows = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kvl = kvlen_ref[b]
    qpos = qpos_ref[b]

    # Dead tiles — logical pages at/after the slot's kv extent — are
    # skipped outright: the index_map clamps them onto the last LIVE
    # page (consecutive identical block indices, so the pipeline elides
    # the page DMA instead of fetching trash) and this guard elides the
    # compute.  A skipped tile leaves the streaming state untouched,
    # which is exactly what the old fetch-then-mask update reduced to
    # (all-NEG_INF scores: alpha = 1, p = 0).
    @pl.when(j * page < kvl)
    def _live_tile():
        # --- per-page score tile (R, page): one MXU dot, not in HBM ---
        q = q_ref[0, 0].astype(jnp.float32)  # (R, D) pre-scaled rows
        k = k_ref[0, 0].astype(jnp.float32)  # (page, D) physical tile
        if binary:
            kb = jnp.where(k > 0, 1.0, -1.0)  # sign_pm1 in-register
        else:
            kb = k
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        # --- masking: validity (kv length) + causality.  Decode
        # (sq == 1) rows share one qpos per slot; sq > 1 chunk rows are
        # causal PER ROW — row r = g * sq + s anchors at qpos + s, the
        # intra-chunk mask keyed on the slot's chunk offset ---
        kpos = (j * page
                + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1))
        if sq > 1:
            qrow = qpos + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page), 0) % sq
        else:
            qrow = qpos
        ok = jnp.logical_and(kpos < kvl, kpos <= qrow)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qrow - window)
        s = jnp.where(ok, s, NEG_INF)

        # --- online softmax update (flash_attention.py pattern) ---
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)  # fully-masked rows stay all-zero
        l_scr[:, 0] = l_scr[:, 0] * alpha + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sq", "binary", "window", "interpret"))
def paged_flash_decode(
    q_rows: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    kv_len: jax.Array,
    q_pos: jax.Array,
    *,
    sq: int = 1,
    binary: bool = False,
    window: int | None = None,
    interpret: bool,
):
    """Fused paged flash-decode over one layer's K/V page pools.

    Args:
      q_rows: (B, H_kv, R, D) float32 — R = GQA-group query rows per kv
        head, PRE-SCALED: dense rows carry q * 1/sqrt(d); binary rows
        carry sign(q) * temp * 1/sqrt(d) (the HAD temperature — per-slot
        running k_scale, or sequential per-query scales under
        spec_verify — is per-row, so it folds into the operand).  For
        ``sq == 1`` all R rows share the slot's decode position; for
        ``sq > 1`` (chunked prefill / speculative verify) R = G * Sq
        with row r = g * sq + s at position ``q_pos[b] + s``.
      k_pages: (P, H_kv, page, D) key pool (one layer; bf16/f32).
      v_pages: (P, H_kv, page, Dv) value pool.
      page_table: (B, NP) int32 logical->physical page map; unallocated
        entries must hold a valid (trash) page index.
      kv_len: (B,) int32 valid tokens per slot (0 = inert row).  Under
        ``sq > 1`` this is the post-write extent INCLUDING the chunk.
      q_pos: (B,) int32 decode position per slot — for ``sq > 1`` the
        chunk's FIRST position (the slot's ``offsets``).
      sq: chunk length folded into the row axis (static).
      binary: binarize the K tile in-register (HAD sign-match scoring).
      interpret: False compiles for the TPU; True runs the Pallas
        interpreter.

    Returns:
      (B, H_kv, R, Dv) float32 attention outputs; inert rows are zeros.
    """
    b, hkv, rows, d = q_rows.shape
    n_pages, _, page, dv = v_pages.shape
    np_ = page_table.shape[1]
    assert k_pages.shape[:3] == (n_pages, hkv, page), (
        k_pages.shape, v_pages.shape)
    assert rows % sq == 0, (rows, sq)
    grid = (b, hkv, np_)
    kern = functools.partial(
        _kernel, page=page, sq=sq, binary=binary, window=window)

    def _kv_map(b_, h, j, pt, kvl, qp):
        # Dead logical pages (at/after the kv extent) clamp onto the
        # slot's last LIVE page: the block index repeats, so the Pallas
        # pipeline skips the redundant DMA and the kernel's `@pl.when`
        # guard skips the compute — trash-extent tiles cost nothing.
        last = jnp.maximum((kvl[b_] - 1) // page, 0)
        return (pt[b_, jnp.where(j * page < kvl[b_], j, last)], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_table, kv_len, q_pos
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda b_, h, j, pt, kvl, qp: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), _kv_map),
            pl.BlockSpec((1, 1, page, dv), _kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rows, dv),
                         lambda b_, h, j, pt, kvl, qp: (b_, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # running max
            pltpu.VMEM((rows, 1), jnp.float32),   # running denominator
            pltpu.VMEM((rows, dv), jnp.float32),  # output accumulator
        ],
    )
    (out,) = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, rows, dv), jnp.float32)],
        interpret=interpret,
        name="paged_flash_decode",
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      q_pos.astype(jnp.int32), q_rows.astype(jnp.float32), k_pages, v_pages)
    return out
