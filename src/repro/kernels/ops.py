"""Jit'd dispatch wrappers for the Pallas kernels.

These present kernel functionality with framework-friendly shapes (padding,
GQA folding, batch flattening) and pick the realization per call, when the
wrapper is traced (never at import): compiled Mosaic on a TPU backend;
off-TPU the flash kernels run their jnp page walk and the CAM kernels the
Pallas interpreter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bacam import pack_bits
from repro.core.topk import NEG_INF
from repro.kernels import bacam_decode as _bdec
from repro.kernels import bacam_mvm as _mvm
from repro.kernels import bacam_topk as _btk
from repro.kernels import bitslice_vmm as _bsv
from repro.kernels import flash_attention as _fla
from repro.kernels import paged_flash_decode as _pfd
from repro.kernels.ref import MASKED_SCORE

__all__ = [
    "bacam_scores",
    "bacam_attention_scores_topk",
    "bacam_attention_scores_topk_packed",
    "bacam_paged_scores_topk",
    "flash_attention",
    "paged_flash_decode",
    "paged_flash_prefill",
    "bitslice_vmm",
    "MASKED_SCORE",
]


def _interpret() -> bool:
    """True off-TPU: decided when a wrapper is traced, so importing this
    module never starts a backend."""
    return jax.default_backend() != "tpu"


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pick_block(n: int, target: int, quantum: int = 8) -> int:
    """Block size: `target` for large inputs, padded-n for small ones."""
    return min(target, _ceil_to(max(n, 1), quantum))


def _pad_axis(x: jax.Array, axis: int, to: int, value=0):
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value)


def bacam_scores(qb: jax.Array, kb: jax.Array, *, block_q=256, block_k=512) -> jax.Array:
    """Binary scores via the Pallas BA-CAM kernel.

    qb: (B*, R, D) ±1; kb: (B*, Skv, D) ±1 (3-D; callers fold GQA/batch).
    Returns (B*, R, Skv) int32.
    """
    b, r, d = qb.shape
    skv = kb.shape[1]
    bq = _pick_block(r, block_q)
    bk = _pick_block(skv, block_k)
    qp = _pad_axis(pack_bits(qb), 1, _ceil_to(r, bq))
    kp = _pad_axis(pack_bits(kb), 1, _ceil_to(skv, bk))
    s = _mvm.bacam_mvm(qp, kp, d=d, block_q=bq, block_k=bk,
                       interpret=_interpret())
    return s[:, :r, :skv]


def bacam_attention_scores_topk(
    qb: jax.Array,
    kb: jax.Array,
    q_pos: jax.Array,
    kv_len: jax.Array,
    *,
    group: int = 16,
    stage1_k: int = 2,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    block_k: int = 512,
):
    """Fused association stage: binary scores + stage-1 top-k candidates.

    qb: (B, R, D) ±1; kb: (B, Skv, D) ±1; q_pos: (B, R) int32;
    kv_len: (B,) or (B, 1) int32.

    Returns (cand_vals f32 with NEG_INF at masked, cand_idx i32), shapes
    (B, R, stage1_k * ceil(Skv/group)).
    """
    d = qb.shape[-1]
    return bacam_attention_scores_topk_packed(
        pack_bits(qb), pack_bits(kb), q_pos, kv_len, d=d,
        group=group, stage1_k=stage1_k, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
    )


def bacam_attention_scores_topk_packed(
    qp: jax.Array,
    kp: jax.Array,
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
):
    """As bacam_attention_scores_topk but on pre-packed uint32 operands
    (the CAMformer KV-cache layout stores keys packed)."""
    b, r, _ = qp.shape
    skv = kp.shape[1]
    bq = _pick_block(r, block_q)
    bk = _pick_block(skv, block_k, quantum=group)
    bk = _ceil_to(bk, group)
    qp = _pad_axis(qp, 1, _ceil_to(r, bq))
    kp = _pad_axis(kp, 1, _ceil_to(skv, bk))
    pos = _pad_axis(q_pos.astype(jnp.int32), 1, _ceil_to(r, bq))
    kvl = jnp.reshape(kv_len.astype(jnp.int32), (b, 1))
    vals, idx = _btk.bacam_topk_stage1(
        qp, kp, pos, kvl,
        d=d, group=group, stage1_k=stage1_k, causal=causal, window=window,
        block_q=bq, block_k=bk, interpret=_interpret(),
    )
    ncand = stage1_k * (-(-skv // group))
    vals = vals[:, :r, :ncand]
    idx = idx[:, :r, :ncand]
    fvals = jnp.where(vals <= MASKED_SCORE // 2, NEG_INF, vals.astype(jnp.float32))
    return fvals, jnp.minimum(idx, skv - 1)


def bacam_paged_scores_topk(
    qp: jax.Array,
    kp_pages: jax.Array,
    page_table: jax.Array,
    kv_len: jax.Array,
    q_pos: jax.Array | None = None,
    *,
    d: int,
    group: int = 16,
    stage1_k: int = 2,
    window: int | None = None,
):
    """Fused paged decode association stage (see bacam_decode.py).

    qp: (B, H_kv, R, W) uint32 decode rows; kp_pages: (P, H_kv, page, W)
    uint32 pool; page_table: (B, NP) int32; kv_len: (B,) int32; q_pos:
    (B,) int32 per-slot query position (default: kv_len - 1, the decode
    tail).

    Returns (cand_vals f32 with NEG_INF at masked, cand_idx i32 logical
    key indices), shapes (B, H_kv, R, stage1_k * NP*page/group).
    """
    page = kp_pages.shape[2]
    np_ = page_table.shape[1]
    if q_pos is None:
        q_pos = kv_len.reshape(-1) - 1
    vals, idx = _bdec.bacam_paged_topk_stage1(
        qp, kp_pages, page_table, kv_len, q_pos,
        d=d, group=group, stage1_k=stage1_k, window=window,
        interpret=_interpret(),
    )
    fvals = jnp.where(vals <= MASKED_SCORE // 2, NEG_INF,
                      vals.astype(jnp.float32))
    return fvals, jnp.clip(idx, 0, np_ * page - 1)


def paged_flash_decode(q, k_pages, v_pages, page_table, kv_len, q_pos, *,
                       temp=None, scale=None, binary=False, window=None,
                       interpret=None):
    """Fused paged flash-decode (kernels/paged_flash_decode.py): decode
    attention through the page table with an online softmax — no
    logical-order gather, no (B, H_kv, NP*page, D) scratch.

    q: (B, H, 1, D) decode queries (GQA: H = G * H_kv);
    k_pages/v_pages: (P, H_kv, page, D[v]) one layer's pools;
    page_table: (B, NP) int32; kv_len: (B,) int32; q_pos: (B,) int32.
    temp: (B, H_kv, G) per-row softmax temperature (binary HAD scoring);
    binary: score on sign(q)/sign(k) instead of q·k.

    Dispatch: the compiled Mosaic kernel on TPU; off-TPU the pure-jnp
    streaming walk (ref.paged_flash_decode_ref — same page sweep and
    accumulation order, XLA-compiled) rather than the Pallas
    interpreter, whose per-grid-cell overhead would misrepresent the
    algorithm.  Pass interpret=True to force the Pallas interpreter
    anyway (CPU CI debugging escape hatch).

    Returns (B, H, 1, Dv) in q's dtype; kv_len == 0 rows are zeros.
    """
    from repro.kernels.ref import paged_flash_decode_ref

    b, h, sq, d = q.shape
    assert sq == 1, "paged_flash_decode is the decode (Sq == 1) hot path"
    hkv = k_pages.shape[1]
    g = h // hkv
    dv = v_pages.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    qr = q.reshape(b, hkv, g, d).astype(jnp.float32)
    if binary:
        qr = jnp.where(qr > 0, 1.0, -1.0)
    # The temperature is per query row: fold it (and the score scale)
    # into the query operand so the stream needs no post-hoc rescale.
    qr = qr * jnp.float32(scale)
    if temp is not None:
        qr = qr * temp.reshape(b, hkv, g, 1).astype(jnp.float32)
    if interpret is not None or not _interpret():
        # explicit interpret=True/False forces the Pallas kernel in that
        # mode; interpret=None on TPU runs it compiled
        out = _pfd.paged_flash_decode(
            qr, k_pages, v_pages, page_table, kv_len.reshape(b),
            q_pos.reshape(b), binary=binary, window=window,
            interpret=bool(interpret) if interpret is not None else False)
    else:
        out = paged_flash_decode_ref(  # off-TPU default: the jnp walk
            qr, k_pages, v_pages, page_table, kv_len.reshape(b),
            q_pos.reshape(b), binary=binary, window=window)
    return out.reshape(b, h, 1, dv).astype(q.dtype)


def paged_flash_prefill(q, k_pages, v_pages, page_table, kv_len, q_pos, *,
                        temp=None, scale=None, binary=False, window=None,
                        interpret=None):
    """Fused paged flash attention for Sq > 1 chunk rows — the chunked
    continuous-prefill and speculative-verify hot path.  Same kernel
    skeleton as ``paged_flash_decode`` (scalar-prefetched page-table
    walk, online-softmax VMEM scratch, dead-tile skip) with the chunk
    folded into the row axis and a per-row causal anchor.

    q: (B, H, Sq, D) chunk queries (GQA: H = G * H_kv);
    k_pages/v_pages: (P, H_kv, page, D[v]) one layer's pools;
    page_table: (B, NP) int32; kv_len: (B,) int32 post-write extent
    INCLUDING the chunk; q_pos: (B,) int32 — the chunk's FIRST position
    per slot (the scheduler's ``offsets``), row s anchors at q_pos + s.
    temp: (B, H_kv, G * Sq) per-row softmax temperature (binary HAD
    scoring; under spec_verify these are the sequential per-query
    running-k_scale values from ``_chunk_scale_seq``) — per-row, so it
    folds into the query operand and the kernel needs no spec awareness.
    binary: score on sign(q)/sign(k) instead of q·k.

    Dispatch triad as ``paged_flash_decode``: compiled Mosaic on TPU,
    the jnp streaming walk off-TPU (identical accumulation order),
    interpret=True forces the Pallas interpreter.

    Returns (B, H, Sq, Dv) in q's dtype; kv_len == 0 rows are zeros.
    """
    from repro.kernels.ref import paged_flash_decode_ref

    b, h, sq, d = q.shape
    hkv = k_pages.shape[1]
    g = h // hkv
    dv = v_pages.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    qr = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    if binary:
        qr = jnp.where(qr > 0, 1.0, -1.0)
    qr = qr * jnp.float32(scale)
    if temp is not None:
        # (B, H_kv, G*Sq) row-major (g, s) — matches the row fold below
        qr = qr * temp.reshape(b, hkv, g, sq, 1).astype(jnp.float32)
    qr = qr.reshape(b, hkv, g * sq, d)  # row r = g_idx * sq + s
    if interpret is not None or not _interpret():
        out = _pfd.paged_flash_decode(
            qr, k_pages, v_pages, page_table, kv_len.reshape(b),
            q_pos.reshape(b), sq=sq, binary=binary, window=window,
            interpret=bool(interpret) if interpret is not None else False)
    else:
        out = paged_flash_decode_ref(  # off-TPU default: the jnp walk
            qr, k_pages, v_pages, page_table, kv_len.reshape(b),
            q_pos.reshape(b), sq=sq, binary=binary, window=window)
    return out.reshape(b, h, sq, dv).astype(q.dtype)


def flash_attention(q, k, v, q_offset=0, *, causal=True, window=None, scale=None,
                    block_q=512, block_k=512):
    """Dense flash attention; q: (B*, Sq, D), k/v: (B*, Skv, D)."""
    b, sq, d = q.shape
    skv = k.shape[1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(skv, block_k)
    qq = _pad_axis(q, 1, _ceil_to(sq, bq))
    kk = _pad_axis(k, 1, _ceil_to(skv, bk))
    vv = _pad_axis(v, 1, _ceil_to(skv, bk))
    # Padded keys are masked because their kpos >= skv > every real qpos
    # only under causal; for non-causal we must mask explicitly via window
    # trick — instead pad K with +inf-distance: set padded kpos invalid by
    # passing kv length through the causal offset. Simplest robust route:
    # pad then slice, masking padded keys via a large negative bias on V=0
    # and K=0 — K=0 gives logits 0 which would leak. So: only allow padding
    # under causal=True or when skv is already aligned.
    if kk.shape[1] != skv and not causal:
        raise ValueError("non-causal flash requires Skv % block_k == 0")
    out = _fla.flash_attention(
        qq, kk, vv, q_offset, causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, interpret=_interpret(),
    )
    return out[:, :sq]


def bitslice_vmm(x_pm1, w_int, *, bits, block_q=256, block_k=512):
    """Exact int VMM via bit slicing; x: (B,R,d) ±1, w_int: (B,N,d)."""
    b, r, d = x_pm1.shape
    n = w_int.shape[1]
    bq = _pick_block(r, block_q)
    bk = _pick_block(n, block_k)
    x = _pad_axis(x_pm1, 1, _ceil_to(r, bq), value=1)
    w = _pad_axis(w_int, 1, _ceil_to(n, bk))
    y = _bsv.bitslice_vmm(x, w, bits=bits, block_q=bq, block_k=bk,
                          interpret=_interpret())
    return y[:, :r, :n]
