"""Model-level attention block: projections, RoPE, sharding strategy, and
dispatch to a pluggable ``AttentionBackend`` (core/backend.py).

The block owns everything physical-realization-*independent* — QKV
projections, RoPE, GQA head layout, the mesh-aware sharding strategy —
and hands the realization itself (cache layout, scoring arithmetic, paged
pools, fused kernels) to the layer's backend:

  * dense:     bf16 K/V caches & pages, softmax attention — baseline.
  * binary:    dense storage, HAD-binarized scoring, full softmax.
  * camformer: keys stored BIT-PACKED (B, H_kv, S, D/32) uint32 — the
               paper's Key SRAM holds binarized keys; 6.25% of the BF16
               footprint (Sec. III-C1).  v stays bf16 (1/1/16 of
               Table II).  A running per-head key scale rides along for
               the softmax temperature.

Per-layer policy: callers pass ``backend=`` (resolved by the model from
``cfg.backend_for(layer)``); without it the block uses the config's
uniform backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.backend import AttentionBackend, get_backend
from repro.models.layers import rope
from repro.models.module import Param
from repro.sharding.partitioning import constrain

__all__ = [
    "attn_specs", "attn_cache_spec", "attention_block",
]


def _resolve_backend(cfg, backend=None) -> AttentionBackend:
    if isinstance(backend, AttentionBackend):
        return backend
    return get_backend(backend or cfg.backend)


def attn_specs(cfg, cross: bool = False):
    d = cfg.d_model
    s = {
        "wq": Param((d, cfg.q_dim), ("embed", "heads")),
        "wk": Param((d, cfg.kv_dim), ("embed", "kv_heads")),
        "wv": Param((d, cfg.kv_dim), ("embed", "kv_heads")),
        "wo": Param((cfg.q_dim, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = Param((cfg.q_dim,), (None,), init="zeros")
        s["bk"] = Param((cfg.kv_dim,), (None,), init="zeros")
        s["bv"] = Param((cfg.kv_dim,), (None,), init="zeros")
    return s


def attn_cache_spec(cfg, batch: int, cache_len: int, dtype, backend=None):
    """ShapeDtypeStructs + logical axes for one layer's self-attn cache
    (delegates to the layer's backend)."""
    return _resolve_backend(cfg, backend).cache_spec(
        cfg, batch, cache_len, dtype)


def _project(p, x, cfg, training: bool = True):
    dt = x.dtype
    b, s, _ = x.shape
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if "bq" in p:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), v + p["bv"].astype(dt)
    q = constrain(q, ("batch", "seq", "heads"))
    k = constrain(k, ("batch", "seq", "kv_heads"))
    v = constrain(v, ("batch", "seq", "kv_heads"))
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    if s > 1:
        strat = _attn_strategy(cfg, training)
        if strat == "heads":  # KV heads divide `model`: fully head-parallel
            q = constrain(q, ("batch", "heads", None, "head_dim"))
            k = constrain(k, ("batch", "kv_heads", None, "head_dim"))
            v = constrain(v, ("batch", "kv_heads", None, "head_dim"))
        elif strat == "qheads":  # GQA: Q heads shard, KV replicates (Megatron)
            q = constrain(q, ("batch", "heads", None, "head_dim"))
            k = constrain(k, ("batch", None, None, "head_dim"))
            v = constrain(v, ("batch", None, None, "head_dim"))
        elif strat == "q_seq":  # inference, heads indivisible: shard Sq —
            # softmax/AV stay local (no backward pass to reshard)
            q = constrain(q, ("batch", None, "att_q_seq", "head_dim"))
            k = constrain(k, ("batch", None, None, "head_dim"))
            v = constrain(v, ("batch", None, None, "head_dim"))
        elif strat == "kv_seq":  # training, heads indivisible: context par.
            q = constrain(q, ("batch", None, None, "head_dim"))
            k = constrain(k, ("batch", None, "att_kv_seq", "head_dim"))
            v = constrain(v, ("batch", None, "att_kv_seq", "head_dim"))
    return q, k, v


def _attn_strategy(cfg, training: bool = True) -> str:
    """Pick the attention sharding strategy from head-count divisibility
    against the ambient mesh's `model` axis (DESIGN.md §5):
      heads:  n_kv_heads % model == 0  — everything head-local.
      qheads: n_heads % model == 0    — Q heads shard, KV replicated.
      else (yi-34b 56H, granite 24H, recurrentgemma 10H):
        q_seq  (inference) — shard the QUERY sequence; softmax and AV are
               fully local since there is no backward pass to reshard.
        kv_seq (training)  — shard the KEY sequence (context parallel);
               GSPMD's backward for q_seq triggers involuntary full-score
               rematerialization, kv_seq keeps bwd local modulo small
               softmax-stat reduces + the AV partial-sum all-reduce.
    """
    env = jax.sharding.get_abstract_mesh()
    if env is None or "model" not in getattr(env, "shape", {}):
        return "none"
    m = env.shape["model"]
    if cfg.n_kv_heads % m == 0:
        return "heads"
    if cfg.n_heads % m == 0:
        return "qheads"
    return "kv_seq" if training else "q_seq"


def _tp_paged_decode(bk, q, cache, k, v, positions, page_table, kv_len,
                     cfg, scale_base):
    """Paged attention over a head-sharded pool slice (the tensor-parallel
    serving seam — see serving/sharded.py for the subsystem design).

    Runs inside the sharded engine's ``shard_map`` body: every pool leaf
    carries only this device's kv-head slice (detected by comparing the
    pool's head extent against ``cfg.n_kv_heads``), while q/k/v from the
    replicated projections carry all heads.  Slice q/k/v to the local
    contiguous head range (q heads are grouped per kv head, so kv heads
    ``[i*hl, (i+1)*hl)`` own q heads ``[i*g*hl, (i+1)*g*hl)``), run the
    backend's paged write+attend unchanged on the slice (all paged
    attention code derives head counts from array shapes), and
    reassemble the per-head outputs with an ``all_gather`` over ``tp``.
    The gather is pure concatenation — no arithmetic — so the block
    output, and every downstream logit and keyed sample, stays
    bit-identical to the single-device engine at any tp degree; a psum
    of partial ``wo`` projections would reorder floating-point sums and
    break token-for-token identity.
    """
    hl = cache["v_pages"].shape[1]  # kv heads local to this device
    g = cfg.n_heads // cfg.n_kv_heads
    i = jax.lax.axis_index("tp")
    q = jax.lax.dynamic_slice_in_dim(q, i * g * hl, g * hl, axis=1)
    k = jax.lax.dynamic_slice_in_dim(k, i * hl, hl, axis=1)
    v = jax.lax.dynamic_slice_in_dim(v, i * hl, hl, axis=1)
    out, new_cache = bk.paged_decode(
        q, cache, k, v, positions, page_table, kv_len, cfg,
        base=scale_base)
    out = jax.lax.all_gather(out, "tp", axis=1, tiled=True)
    return out, new_cache


def attention_block(
    p,
    x: jax.Array,
    cfg,
    *,
    positions: jax.Array,
    cache=None,
    cache_index=None,
    kv_len=None,
    kv_positions=None,
    page_table=None,
    scale_base=None,
    causal: bool = True,
    window: int | None = None,
    cross_kv=None,
    backend=None,
):
    """Full attention sub-block. Returns (out (B,S,d_model), new_cache).

    Modes of operation:
      train:          cache=None                       — full self-attention
      prefill:        cache empty, cache_index=0       — attn + cache write
      decode:         cache filled, cache_index=pos    — 1-token query
      paged serving:  cache is a page-pool dict, page_table set — prefill
                      chunks and decode both splice into pages and attend
                      through the page table (no contiguous KV buffer)
      cross-attention: cross_kv=(k, v) precomputed     — no cache write

    ``backend`` selects the physical realization (an AttentionBackend or
    registry name); default is the config's uniform backend.
    """
    bk = _resolve_backend(cfg, backend)
    b, s, _ = x.shape
    dt = x.dtype
    with jax.named_scope("attn_proj"):
        q, k, v = _project(p, x, cfg,
                           training=cache is None and cross_kv is None)
        if cross_kv is None and getattr(cfg, "use_rope", True):
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)

    if cross_kv is not None:
        k, v = cross_kv
        # Paper Sec. IV-C: enc-dec models use non-causal CAM search over
        # encoder keys — the backend applies to cross-attention too.
        out = bk.prefill(q, k, v, cfg, causal=False)
    else:
        if cache is not None and "v" not in cache and page_table is None:
            # a paged pool (k_pages/kp_pages + v_pages) reached the
            # contiguous path — fail loudly, not with a KeyError below
            raise ValueError("paged cache needs page_table and kv_len")
        if page_table is not None and cache is not None:
            if kv_len is None:
                raise ValueError("paged cache needs page_table and kv_len")
            if cache["v_pages"].shape[1] != cfg.n_kv_heads:
                # head-sharded pool slice: inside the tensor-parallel
                # engine's shard_map body (serving/sharded.py)
                out, new_cache = _tp_paged_decode(
                    bk, q, cache, k, v, positions, page_table, kv_len,
                    cfg, scale_base)
            else:
                out, new_cache = bk.paged_decode(
                    q, cache, k, v, positions, page_table, kv_len, cfg,
                    base=scale_base)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.q_dim)
            out = constrain(out, ("batch", "seq", "heads"))
            with jax.named_scope("attn_proj"):
                return (out @ p["wo"].astype(dt)), new_cache
        index = cache_index if cache_index is not None else jnp.int32(0)
        if cache is not None and kv_len is not None:
            # decode / cached path: attend over the (partially valid) cache
            out, cache = bk.decode(
                q, cache, k, v, index, kv_len, positions, cfg,
                kv_positions=kv_positions, window=window)
        else:
            # train / prefill: attend over freshly-computed K/V
            cache = bk.write_cache(cache, k, v, index, cfg)
            out = bk.prefill(
                q, k, v, cfg, causal=causal,
                positions=positions, window=window or cfg.window)

    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.q_dim)
    # Preserve the attention-interior layout on the way out: under q_seq the
    # output stays sequence-sharded (forcing head-sharding here would make
    # GSPMD gather the full score tensor to replicate the sequence axis).
    if s > 1 and _attn_strategy(cfg, cache is None and cross_kv is None) == "q_seq":
        out = constrain(out, ("batch", "att_q_seq", "heads"))
    else:
        out = constrain(out, ("batch", "seq", "heads"))
    with jax.named_scope("attn_proj"):
        return (out @ p["wo"].astype(dt)), cache
