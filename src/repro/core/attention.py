"""CAMformer attention: Eq. 1 of the paper as a composable JAX module.

    CAMformer-Attn(Q, K, V) = SoftMax(Top-32(QK^T)) . V

with QK^T computed on binarized operands by the BA-CAM device model (or its
Pallas kernel) and Top-32 realized as the two-stage hierarchical top-k.

Three modes, forming the ablation ladder of Tables III/IV:

  * ``dense``     — standard softmax attention (the oracle / teacher).
  * ``binary``    — HAD-binarized Q/K, *full* softmax over all N binary
                    scores (single-stage upper bound, no sparsity).
  * ``camformer`` — binary scores -> two-stage top-k -> softmax over the k
                    survivors -> sparse V contextualization (the paper).

GQA is supported natively: q may have H = G * H_kv heads against H_kv
key/value heads; K/V are never materialized repeated.

Ordering note (faithfulness): the CAM selects on the *raw* binary score
(matchline voltage).  HAD's per-tensor scales therefore only enter as a
softmax temperature, never in the selection — we reduce the key scale per
head (not per row) so selection ordering matches the hardware exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bacam
from repro.core.binarize import binarize_qk
from repro.core.topk import NEG_INF, two_stage_topk

__all__ = [
    "AttentionSpec", "attention", "binary_paged_attention",
    "camformer_paged_attention", "dense_reference", "make_mask",
    "topk_softmax_weights",
]


def topk_softmax_weights(top_v, temp, scale):
    """Softmax over top-k survivors (the hardware's LUT stage).

    top_v: (..., k) raw binary scores with NEG_INF at masked entries;
    temp: HAD temperature, broadcastable to top_v; scale: 1/sqrt(d).
    Returns (weights, valid) — weights are exactly 0 at invalid entries
    (callers must also zero any values gathered for them before a
    fused multiply-add, to avoid reading garbage at weight 0).
    """
    valid = top_v > NEG_INF / 2
    logits = jnp.where(valid, top_v * temp * scale, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.where(valid, w, 0.0), valid


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """Configuration of the attention operator (first-class feature)."""

    mode: str = "dense"  # dense | binary | camformer
    k_top: int = 32
    group_size: int = 16  # CAM_H
    stage1_k: int = 2
    # Device-fidelity knobs (benchmarks only; None/0.0 == exact integer path)
    adc_bits: Optional[int] = None
    noise_sigma: float = 0.0
    cam_w: int = bacam.CAM_W
    # Straight-through estimator for training binarized models (HAD)
    trainable_binarize: bool = False
    # Route binary scoring through the Pallas BA-CAM kernel
    use_kernel: bool = False

    def replace(self, **kw) -> "AttentionSpec":
        return dataclasses.replace(self, **kw)


def make_mask(
    sq: int,
    skv: int,
    *,
    causal: bool = True,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    kv_valid: jax.Array | None = None,
    window: int | None = None,
):
    """Build a boolean validity mask, broadcastable to (B, 1, Sq, Skv).

    Built from iota comparisons (never a materialized (S,S) constant in HBM —
    XLA fuses these).  ``q_positions``/``kv_positions`` may be traced (decode
    against a rotating cache); defaults are arange.
    """
    if q_positions is None:
        q_positions = jnp.arange(sq, dtype=jnp.int32)[None, :]
    if kv_positions is None:
        kv_positions = jnp.arange(skv, dtype=jnp.int32)[None, :]
    qp = q_positions[:, :, None]  # (B?, Sq, 1)
    kp = kv_positions[:, None, :]  # (B?, 1, Skv)
    mask = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), dtype=bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    return mask[:, None]  # (B?, 1, Sq, Skv) — head axis broadcasts


def _split_gqa(q: jax.Array, h_kv: int) -> jax.Array:
    """(B, H, Sq, D) -> (B, H_kv, G, Sq, D) without copying KV."""
    b, h, sq, d = q.shape
    if h % h_kv != 0:
        raise ValueError(f"H={h} not divisible by H_kv={h_kv}")
    return q.reshape(b, h_kv, h // h_kv, sq, d)


def _binary_scores(qg, k, spec: AttentionSpec, rng):
    """Binary scores (B, Hkv, G, Sq, Skv) + softmax temperature scale."""
    qb, kb, q_scale, k_scale = binarize_qk(
        qg, k, trainable=spec.trainable_binarize, with_scales=True
    )
    if spec.adc_bits is None and spec.noise_sigma == 0.0:
        if spec.use_kernel:
            from repro.kernels import ops as kops  # local import: no cycle

            b, hkv, g, sq, d_ = qb.shape
            skv = kb.shape[-2]
            s3 = kops.bacam_scores(
                qb.reshape(b * hkv, g * sq, d_), kb.reshape(b * hkv, skv, d_)
            )
            scores = s3.reshape(b, hkv, g, sq, skv)
        else:
            scores = bacam.bacam_scores(qb[...], kb[:, :, None], exact=True)
    else:
        kb = kb[:, :, None]  # broadcast against the GQA group axis
        scores = bacam.bacam_scores(
            qb,
            kb,
            cam_w=spec.cam_w,
            adc_bits=spec.adc_bits,
            noise_sigma=spec.noise_sigma,
            rng=rng,
            exact=False,
        )
    # HAD temperature: per-(query-row) q scale (order-preserving per row) and
    # per-head k scale (selection on raw scores == hardware).
    k_scale_head = jnp.mean(k_scale, axis=-2, keepdims=True)  # (B,Hkv,1,1)
    temp = q_scale * k_scale_head[..., None, :, :]  # (B,Hkv,G,Sq,1)
    return scores.astype(jnp.float32), temp


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    spec: AttentionSpec = AttentionSpec(),
    *,
    causal: bool = True,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    kv_valid: jax.Array | None = None,
    window: int | None = None,
    scale: float | None = None,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Multi-head (GQA) attention with selectable CAMformer modes.

    Args:
      q: (B, H, Sq, D); k: (B, H_kv, Skv, D); v: (B, H_kv, Skv, Dv).
      causal/window/kv_valid/positions: masking controls (see make_mask).
      scale: score scale; default 1/sqrt(D).

    Returns: (B, H, Sq, Dv) in q's dtype.
    """
    b, h, sq, d = q.shape
    _, h_kv, skv, dv = v.shape
    if scale is None:
        scale = 1.0 / (d**0.5)
    qg = _split_gqa(q, h_kv)
    mask = make_mask(
        sq,
        skv,
        causal=causal,
        q_positions=q_positions,
        kv_positions=kv_positions,
        kv_valid=kv_valid,
        window=window,
    )  # (B?,1,Sq,Skv)
    mask5 = mask[:, :, None]  # (B?,1,1,Sq,Skv) — broadcast over (Hkv, G)

    if spec.mode == "dense":
        logits = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
        logits = jnp.where(mask5, logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", w.astype(v.dtype), v)
        return out.reshape(b, h, sq, dv).astype(q.dtype)

    scores, temp = _binary_scores(qg, k, spec, rng)
    # XNOR-Net/HAD dequant: q.k ~ alpha_q*alpha_k*(qb.kb)  =>  logit = s*temp*scale
    logits = scores * temp * scale

    if spec.mode == "binary":
        logits = jnp.where(mask5, logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", w.astype(v.dtype), v)
        return out.reshape(b, h, sq, dv).astype(q.dtype)

    if spec.mode != "camformer":
        raise ValueError(f"unknown attention mode {spec.mode!r}")

    # --- CAMformer: select on RAW binary scores (hardware ordering) ---
    raw = jnp.where(mask5, scores, NEG_INF)
    top_v, top_i = two_stage_topk(
        raw, k=spec.k_top, group_size=spec.group_size, stage1_k=spec.stage1_k
    )  # (B,Hkv,G,Sq,K)
    valid = top_v > NEG_INF / 2
    # Temperature applies to the k survivors (softmax LUT stage).
    sel_logits = jnp.where(valid, top_v * temp * scale, NEG_INF)
    w = jax.nn.softmax(sel_logits, axis=-1)  # rows with <k valid stay correct
    # Sparse contextualization: gather only the k selected V rows.
    v_exp = v[:, :, None, None]  # (B,Hkv,1,1,Skv,Dv)
    idx = top_i[..., None]  # (B,Hkv,G,Sq,K,1)
    v_sel = jnp.take_along_axis(v_exp, idx, axis=-2)  # (B,Hkv,G,Sq,K,Dv)
    out = jnp.einsum("bhgqk,bhgqkd->bhgqd", w.astype(v.dtype), v_sel)
    return out.reshape(b, h, sq, dv).astype(q.dtype)


def binary_paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    k_scale: jax.Array,
    page_table: jax.Array,
    kv_len: jax.Array,
    q_positions: jax.Array,
    spec: AttentionSpec = AttentionSpec(mode="binary"),
    *,
    window: int | None = None,
    scale: float | None = None,
    impl: str = "fused",
) -> jax.Array:
    """Binary (HAD sign-match, FULL softmax) attention against the paged
    dense-storage K/V pools — the single-stage ablation point on the
    serving path.

    Scoring binarizes Q and the paged keys at attend time
    (``core/binarize.sign_pm1``); the softmax temperature is
    ``q_scale * k_scale`` with ``k_scale`` the slot's RUNNING per-head
    key scale maintained at page-write time (the camformer bookkeeping,
    shared via ``BinaryBackend.page_spec``) — a streamable per-slot
    statistic, unlike recomputing a mean over gathered rows, so the
    fused and gather realizations score identically and trash-page
    garbage never leaks into the temperature.

    ``impl="fused"`` runs the paged flash kernel
    (kernels/paged_flash_decode.py) with in-register K binarization —
    bytes proportional to live pages: decode rows (Sq == 1) through
    ``kops.paged_flash_decode``, chunk rows (Sq > 1: chunked prefill and
    speculative verify, whose per-query sequential scales arrive as a
    3-D ``k_scale`` and fold into the temperature) through
    ``kops.paged_flash_prefill`` with the per-row causal anchor.
    ``impl="gather"`` gathers the pages into logical order and runs the
    same masked full softmax in XLA (the pinned reference).

    Shapes as ``camformer_paged_attention`` but with dense
    ``k_pages`` (P, H_kv, page, D).  Returns (B, H, Sq, Dv).
    """
    from repro.core.binarize import sign_pm1

    b, h, sq, d = q.shape
    _, hkv, page, dv = v_pages.shape
    g = h // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    kv_len = kv_len.reshape(b).astype(jnp.int32)
    q_scale = jnp.mean(jnp.abs(q.astype(jnp.float32)), axis=-1)  # (B,H,Sq)
    # k_scale: (B, Hkv) per-slot running scale, or (B, Hkv, Sq) per-QUERY
    # scales (speculative verify chunks: column j's scale covers keys up
    # to its own position — sequential-decode semantics).
    ks = k_scale.astype(jnp.float32)
    if ks.ndim == 2:
        ks = ks[:, :, None]
    ks = jnp.broadcast_to(ks[:, :, None, :], (b, hkv, g, sq))
    temp = q_scale.reshape(b, hkv, g * sq) * ks.reshape(b, hkv, g * sq)

    if impl == "fused":
        from repro.kernels import ops as kops  # local import: no cycle

        if sq == 1:
            return kops.paged_flash_decode(
                q, k_pages, v_pages, page_table, kv_len,
                q_positions.reshape(b).astype(jnp.int32),
                temp=temp, binary=True, window=window, scale=scale)
        # Chunk rows: positions are contiguous from the slot's offset,
        # so the kernel takes the first position + per-row anchors.
        return kops.paged_flash_prefill(
            q, k_pages, v_pages, page_table, kv_len,
            q_positions[:, 0].astype(jnp.int32),
            temp=temp, binary=True, window=window, scale=scale)

    # Gather reference: logical-order pages, same scoring arithmetic.
    from repro.kernels.ref import paged_gather_ref

    ck = paged_gather_ref(k_pages, page_table)  # (B, H_kv, S_log, D)
    cv = paged_gather_ref(v_pages, page_table)
    s_log = ck.shape[2]
    qb = sign_pm1(q.astype(jnp.float32)).reshape(b, hkv, g * sq, d)
    kb = sign_pm1(ck.astype(jnp.float32))
    scores = jnp.einsum("bhrd,bhkd->bhrk", qb, kb)
    kpos = jnp.arange(s_log, dtype=jnp.int32)[None, None, None]
    qpos = jnp.broadcast_to(q_positions[:, None, :], (b, hkv, sq))
    qpos = jnp.broadcast_to(qpos[:, :, None, :], (b, hkv, g, sq)).reshape(
        b, hkv, g * sq)[..., None]
    ok = (kpos < kv_len.reshape(b, 1, 1, 1)) & (kpos <= qpos)
    if window is not None:
        ok = ok & (kpos > qpos - window)
    logits = scores * temp[..., None] * scale
    logits = jnp.where(ok, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(ok, w, 0.0)  # inert rows: all-zero weights, zero out
    out = jnp.einsum("bhrk,bhkd->bhrd", w, cv.astype(jnp.float32))
    return out.reshape(b, h, sq, dv).astype(q.dtype)


def camformer_paged_attention(
    q: jax.Array,
    kp_pages: jax.Array,
    v_pages: jax.Array,
    k_scale: jax.Array,
    page_table: jax.Array,
    kv_len: jax.Array,
    q_positions: jax.Array,
    spec: AttentionSpec = AttentionSpec(mode="camformer"),
    *,
    window: int | None = None,
    scale: float | None = None,
    impl: str = "fused",
) -> jax.Array:
    """CAMformer attention against a paged, bit-packed KV cache.

    The serving-engine entry point (Eq. 1 over a page-table-indirected Key
    SRAM): binary scores + two-stage top-k select on the paged pools, then
    softmax over the k survivors and a sparse gather of ONLY the selected V
    rows straight out of the paged pool — no per-slot contiguous ``max_len``
    key/value buffer is ever materialized.

    Decode rows (Sq == 1, ``impl="fused"`` — the default) run the fused
    Pallas paged kernel (kernels/bacam_decode.py): scoring + stage-1
    top-k happen page-local via scalar-prefetched page-table DMA and
    only stage-1 candidates reach this level.  Prefill chunks (Sq > 1)
    and ``impl="gather"`` (the selectable XLA reference,
    ``ModelConfig.paged_impl``) gather the packed keys — 1 bit/element,
    6.25% of bf16 — into logical order and run the same two-stage
    selection in XLA.

    Args:
      q: (B, H, Sq, D) queries (GQA: H = G * H_kv).
      kp_pages: (P, H_kv, page, D/32) uint32 packed key pool (one layer).
      v_pages: (P, H_kv, page, Dv) value pool.
      k_scale: (B, H_kv) running per-slot key scale (softmax temperature).
      page_table: (B, NP) int32 logical->physical page map (trash-paged
        rows for unallocated entries).
      kv_len: (B,) int32 valid tokens per slot.
      q_positions: (B, Sq) int32 query positions.

    Returns: (B, H, Sq, Dv) in q's dtype.
    """
    from repro.core.binarize import sign_pm1

    b, h, sq, d = q.shape
    _, hkv, page, dv = v_pages.shape
    g = h // hkv
    np_ = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    with jax.named_scope("cam_search"):
        qb = sign_pm1(q.astype(jnp.float32))
        q_scale = jnp.mean(jnp.abs(q.astype(jnp.float32)), axis=-1)  # (B,H,Sq)
        qp = bacam.pack_bits(qb).reshape(b, hkv, g * sq, d // 32)
        kv_len = kv_len.reshape(b).astype(jnp.int32)

        if sq == 1 and impl == "fused":
            # Decode fast path: fused paged scoring + stage-1 top-k kernel.
            from repro.kernels import ops as kops  # local import: no cycle

            cand_v, cand_i = kops.bacam_paged_scores_topk(
                qp, kp_pages, page_table, kv_len,
                q_positions.reshape(b).astype(jnp.int32),
                d=d, group=spec.group_size, stage1_k=spec.stage1_k,
                window=window)
            k_eff = min(spec.k_top, cand_v.shape[-1])
            top_v, sel = jax.lax.top_k(cand_v, k_eff)
            top_i = jnp.take_along_axis(cand_i, sel, axis=-1)
        else:
            # Prefill chunk: gather packed key pages into logical order.
            from repro.kernels.ref import paged_gather_ref

            kp = paged_gather_ref(kp_pages, page_table)  # (B, H_kv, S_log, W)
            scores = bacam.hamming_scores_packed(qp, kp, d)  # (B,Hkv,G*Sq,S)
            kpos = jnp.arange(np_ * page, dtype=jnp.int32)[None, None, None]
            qpos = jnp.broadcast_to(q_positions[:, None, :], (b, hkv, sq))
            qpos = jnp.broadcast_to(qpos[:, :, None, :], (b, hkv, g, sq)).reshape(
                b, hkv, g * sq)[..., None]
            ok = (kpos < kv_len.reshape(b, 1, 1, 1)) & (kpos <= qpos)
            if window is not None:
                ok = ok & (kpos > qpos - window)
            masked = jnp.where(ok, scores.astype(jnp.float32), NEG_INF)
            top_v, top_i = two_stage_topk(
                masked, k=spec.k_top, group_size=spec.group_size,
                stage1_k=spec.stage1_k)

    with jax.named_scope("contextualize"):
        # sparse V contextualization straight from the paged pool
        pg = top_i // page  # logical page of each selected key
        row = top_i % page
        phys = page_table[jnp.arange(b)[:, None, None, None], pg]  # (B,Hkv,R,K)
        v_sel = jax.vmap(  # per-kv-head gather: pool is (P, page, Dv) per head
            lambda vh, ph, rh: vh[ph, rh], in_axes=(1, 1, 1), out_axes=1
        )(v_pages, phys, row)  # (B, H_kv, R, K, Dv)

        # per-slot (B, Hkv) or per-query (B, Hkv, Sq) — see
        # binary_paged_attention
        ks = k_scale.astype(jnp.float32)
        if ks.ndim == 2:
            ks = ks[:, :, None]
        ks = jnp.broadcast_to(ks[:, :, None, :], (b, hkv, g, sq))
        temp = (q_scale.reshape(b, hkv, g * sq)
                * ks.reshape(b, hkv, g * sq))[..., None]
        w, _ = topk_softmax_weights(top_v, temp, scale)
        out = jnp.einsum("bhrk,bhrkd->bhrd", w.astype(v_pages.dtype), v_sel)
    return out.reshape(b, h, sq, dv).astype(q.dtype)


def dense_reference(q, k, v, *, causal=True, scale=None, window=None):
    """Naive full-precision softmax attention oracle (tests/teacher)."""
    return attention(
        q, k, v, AttentionSpec(mode="dense"), causal=causal, scale=scale, window=window
    )
