"""Decoder-only transformer LM (dense / GQA / MoE / sliding-window).

Scan-over-layers with remat: block parameters are stacked along a leading
`layers` axis and the stack runs under jax.lax.scan, keeping HLO size O(1)
in depth (essential for 80-layer configs at 512 devices) with full
activation rematerialization in the backward pass.

Serves as the backbone for qwen1.5-110b, mistral-nemo-12b, yi-34b,
codeqwen1.5-7b, moonshot-v1-16b-a3b, granite-moe-3b-a800m, and (via vlm.py /
encdec.py) llava-next and whisper.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.backend import backends_for
from repro.models import layers as L
from repro.models import moe as M
from repro.models.attention import attn_specs, attention_block
from repro.models.module import Param, is_param
from repro.sharding.partitioning import constrain

__all__ = ["ModelDef", "stack_specs", "lm_specs", "lm_hidden", "lm_loss",
           "lm_prefill", "lm_decode", "lm_cache_specs", "lm_page_specs",
           "lm_prefill_paged", "lm_decode_paged", "lm_verify_paged",
           "dtype_of"]


class ModelDef(NamedTuple):
    """Uniform model interface used by the launcher / trainer / server."""

    specs: Callable[..., Any]
    loss: Callable[..., Any]  # (params, batch, cfg) -> (loss, aux)
    prefill: Callable[..., Any]  # (params, batch, cache, cfg) -> (logits, cache)
    decode: Callable[..., Any]  # (params, tokens, pos, kv_len, cache, cfg) -> (logits, cache)
    cache_specs: Callable[..., Any]  # (cfg, batch, cache_len) -> tree of (SDS, axes)
    # Paged-serving interface (None for families without a paged cache):
    # page_specs(cfg, n_pages, page_size, max_batch) -> tree of (SDS, axes)
    # prefill_paged(params, batch{tokens,lens[,offsets]}, pools, table, cfg)
    # decode_paged(params, tokens, pos, kv_len, pools, table, cfg[, base])
    # verify_paged(params, batch, pools, table, cfg) -> ((B,S,V), pools)
    page_specs: Optional[Callable[..., Any]] = None
    prefill_paged: Optional[Callable[..., Any]] = None
    decode_paged: Optional[Callable[..., Any]] = None
    verify_paged: Optional[Callable[..., Any]] = None


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


def stack_specs(specs, n: int):
    """Add a leading `layers` axis of size n to every Param in the tree."""
    return jax.tree.map(
        lambda p: Param((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale),
        specs,
        is_leaf=is_param,
    )


def _block_specs(cfg):
    s = {"ln1": L.norm_specs(cfg), "attn": attn_specs(cfg), "ln2": L.norm_specs(cfg)}
    s["ffn"] = M.moe_specs(cfg) if cfg.n_experts else L.mlp_specs(cfg)
    return s


def _apply_block(p, x, cfg, *, positions, cache=None, cache_index=None,
                 kv_len=None, page_table=None, scale_base=None, causal=True,
                 backend=None):
    h, new_cache = attention_block(
        p["attn"], L.apply_norm(p["ln1"], x, cfg), cfg,
        positions=positions, cache=cache, cache_index=cache_index,
        kv_len=kv_len, page_table=page_table, scale_base=scale_base,
        causal=causal, backend=backend)
    x = constrain(x + h, ("batch", "res_seq", "embed"))
    with jax.named_scope("mlp"):
        ff_in = L.apply_norm(p["ln2"], x, cfg)
        if cfg.n_experts:
            ff, aux = M.apply_moe(p["ffn"], ff_in, cfg)
        else:
            ff, aux = L.apply_mlp(p["ffn"], ff_in, cfg), {}
    x = constrain(x + ff, ("batch", "res_seq", "embed"))
    return x, new_cache, aux


def lm_specs(cfg):
    return {
        "embed": L.embed_specs(cfg),
        "blocks": stack_specs(_block_specs(cfg), cfg.n_layers),
        "ln_f": L.norm_specs(cfg),
    }


def _scan_blocks(params, x, cfg, *, positions, caches=None, cache_index=None,
                 kv_len=None, page_table=None, scale_base=None, causal=True):
    """Run the layer stack; returns (x, new_caches, aux_sums).

    Uniform-backend stacks run under jax.lax.scan with layer-stacked
    caches.  A per-layer backend policy (cfg.layer_backends) makes cache
    pytrees heterogeneous across layers, so those stacks unroll: caches
    are a TUPLE of per-layer trees and each layer binds its own backend.
    """
    backends = backends_for(cfg)
    # the same predicate decides cache layout in lm_cache_specs/lm_page_specs
    uniform = cfg.uniform_backend is not None
    per_layer_caches = isinstance(caches, (tuple, list))

    def body(carry, xs, backend=backends[0]):
        h, aux_sum = carry
        layer_p, layer_cache = xs
        if not isinstance(layer_cache, dict):  # train: no cache threaded
            layer_cache = None
        h, new_cache, aux = _apply_block(
            layer_p, h, cfg, positions=positions, cache=layer_cache,
            cache_index=cache_index, kv_len=kv_len, page_table=page_table,
            scale_base=scale_base, causal=causal, backend=backend)
        aux_vec = jnp.stack(
            [aux.get("moe_aux_loss", jnp.float32(0)),
             aux.get("moe_drop_frac", jnp.float32(0))])
        return (h, aux_sum + aux_vec), new_cache

    if cfg.scan_layers and uniform and not per_layer_caches:
        body_fn = body
        if cfg.remat == "full":
            body_fn = jax.checkpoint(body, prevent_cse=False)
        (x, aux_sum), new_caches = jax.lax.scan(
            body_fn, (x, jnp.zeros(2, jnp.float32)), (params["blocks"], caches))
    else:
        aux_sum = jnp.zeros(2, jnp.float32)
        outs = []
        for i in range(cfg.n_layers):
            layer_p = jax.tree.map(lambda a: a[i], params["blocks"])
            if caches is None:
                layer_c = None
            elif per_layer_caches:
                layer_c = caches[i]
            else:
                layer_c = jax.tree.map(lambda a: a[i], caches)
            # bind the layer's backend BEFORE any transform so the object
            # never flows through tracing as a pytree input
            bound = functools.partial(body, backend=backends[i])
            if cfg.remat == "full":
                bound = jax.checkpoint(bound, prevent_cse=False)
            (x, aux_sum), nc = bound((x, aux_sum), (layer_p, layer_c))
            outs.append(nc)
        if caches is None:
            new_caches = None
        elif per_layer_caches:
            new_caches = tuple(outs)
        else:
            new_caches = jax.tree.map(lambda *cs: jnp.stack(cs), *outs)
    aux = {"moe_aux_loss": aux_sum[0] / cfg.n_layers,
           "moe_drop_frac": aux_sum[1] / cfg.n_layers}
    return x, new_caches, aux


def _none_caches(cfg):
    """A scan-compatible stand-in when no cache is threaded (train)."""
    return jnp.zeros((cfg.n_layers, 0), jnp.float32)


def lm_hidden(params, tokens, cfg, *, positions=None, caches=None,
              cache_index=None, kv_len=None, page_table=None,
              scale_base=None, causal=True, prefix_embeds=None):
    """tokens (B, S) -> final hidden states (B, S[+P], d)."""
    dt = dtype_of(cfg)
    with jax.named_scope("embed"):
        x = L.embed_lookup(params["embed"], tokens, cfg, dt)
    if prefix_embeds is not None:  # VLM: prepend patch embeddings
        x = jnp.concatenate([prefix_embeds.astype(dt), x], axis=1)
    x = constrain(x, ("batch", "res_seq", "embed"))
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if getattr(cfg, "abs_pos", None) == "sinusoidal" or not getattr(cfg, "use_rope", True):
        x = x + L.sinusoidal_positions(positions, cfg.d_model).astype(dt)
    if caches is None:
        caches = _none_caches(cfg)
    x, new_caches, aux = _scan_blocks(
        params, x, cfg, positions=positions, caches=caches,
        cache_index=cache_index, kv_len=kv_len, page_table=page_table,
        scale_base=scale_base, causal=causal)
    x = L.apply_norm(params["ln_f"], x, cfg)
    # loss/head consumers slice along seq: hand them a seq-replicated copy
    x = constrain(x, ("batch", None, "embed"))
    return x, new_caches, aux


def lm_loss(params, batch, cfg):
    """Causal LM loss. batch: tokens (B,S), labels (B,S), [loss_mask]."""
    x, _, aux = lm_hidden(params, batch["tokens"], cfg)
    loss, stats = L.chunked_cross_entropy(
        x, params["embed"], batch["labels"], cfg,
        loss_mask=batch.get("loss_mask"))
    if cfg.n_experts:
        loss = loss + 0.01 * aux["moe_aux_loss"]
    stats.update(aux)
    return loss, stats


def _stack_layer_specs(cfg, one):
    """Add the leading `layers` axis to a single-layer spec tree."""
    return {
        k: (jax.ShapeDtypeStruct((cfg.n_layers,) + sds.shape, sds.dtype),
            ("layers",) + axes)
        for k, (sds, axes) in one.items()
    }


def lm_cache_specs(cfg, batch: int, cache_len: int):
    """Cache specs: layer-stacked (scan-compatible) for a uniform backend;
    a TUPLE of per-layer spec trees under a mixed layer_backends policy
    (layouts differ per layer, so the stack unrolls)."""
    dt = dtype_of(cfg)
    bks = backends_for(cfg)
    if cfg.uniform_backend is not None:
        return _stack_layer_specs(cfg, bks[0].cache_spec(cfg, batch,
                                                         cache_len, dt))
    return tuple(bk.cache_spec(cfg, batch, cache_len, dt) for bk in bks)


def lm_prefill(params, batch, caches, cfg):
    """Prefill: forward writing the cache at index 0.

    With cfg.prefill_chunk set, the prompt is processed in chunks that
    attend to the cache-so-far (activation memory bounded by the chunk —
    the standard chunked-prefill serving technique).

    Returns (last-token logits (B, V), caches)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    chunk = cfg.prefill_chunk
    if (chunk and s > chunk and s % chunk == 0
            and batch.get("image_embeds") is None):
        n = s // chunk
        toks = tokens.reshape(b, n, chunk).swapaxes(0, 1)  # (n, B, chunk)

        def body(carry, xs):
            cs, _ = carry
            i, tk = xs
            pos = (i * chunk
                   + jnp.arange(chunk, dtype=jnp.int32))[None].repeat(b, 0)
            kvl = jnp.full((b,), (i + 1) * chunk, jnp.int32)
            x, cs, _ = lm_hidden(
                params, tk, cfg, positions=pos, caches=cs,
                cache_index=(i * chunk).astype(jnp.int32), kv_len=kvl,
                causal=True)
            return (cs, x[:, -1]), None

        dt = dtype_of(cfg)
        init = (caches, jnp.zeros((b, cfg.d_model), dt))
        (caches, last), _ = jax.lax.scan(
            body, init, (jnp.arange(n, dtype=jnp.int32), toks))
        logits = _last_logits(params, last[:, None], cfg)
        return logits, caches

    x, caches, _ = lm_hidden(
        params, tokens, cfg, caches=caches, cache_index=jnp.int32(0),
        kv_len=None, causal=True,
        prefix_embeds=batch.get("image_embeds"))
    logits = _last_logits(params, x, cfg)
    return logits, caches


def lm_page_specs(cfg, n_pages: int, page_size: int, max_batch: int):
    """Paged-pool specs (serving/kv_cache.py layout): layer-stacked for a
    uniform backend, per-layer tuple under a mixed policy — dense bf16
    pages and bit-packed CAM pages then live side by side in one pool."""
    dt = dtype_of(cfg)
    bks = backends_for(cfg)
    if cfg.uniform_backend is not None:
        return _stack_layer_specs(cfg, bks[0].page_spec(cfg, n_pages,
                                                        page_size, max_batch,
                                                        dt))
    return tuple(bk.page_spec(cfg, n_pages, page_size, max_batch, dt)
                 for bk in bks)


def _paged_suffix_hidden(params, batch, caches, page_table, cfg):
    """Shared hidden path of the paged Sq>1 seam (prefill + verify).

    batch: tokens (B, S) right-padded suffixes at positions
    ``offsets + arange(S)``, lens (B,) TOTAL valid lengths, optional
    offsets / scale_base — see ``lm_prefill_paged``.  Returns the full
    per-position hidden states (x (B, S, d), pools).
    """
    tokens, lens = batch["tokens"], batch["lens"].astype(jnp.int32)
    b, s = tokens.shape
    offsets = batch.get("offsets")
    offsets = (jnp.zeros((b,), jnp.int32) if offsets is None
               else offsets.astype(jnp.int32))
    scale_base = batch.get("scale_base")
    scale_base = (offsets if scale_base is None
                  else scale_base.astype(jnp.int32))
    chunk = cfg.prefill_chunk
    if chunk and s > chunk and s % chunk == 0:
        n = s // chunk
        toks = tokens.reshape(b, n, chunk).swapaxes(0, 1)  # (n, B, chunk)

        def body(cs, xs):
            i, tk = xs
            pos = (offsets[:, None] + i * chunk
                   + jnp.arange(chunk, dtype=jnp.int32)[None])
            x, cs, _ = lm_hidden(
                params, tk, cfg, positions=pos, caches=cs, kv_len=lens,
                page_table=page_table, scale_base=scale_base, causal=True)
            return cs, x

        caches, xs = jax.lax.scan(
            body, caches, (jnp.arange(n, dtype=jnp.int32), toks))
        x = xs.swapaxes(0, 1).reshape(b, s, -1)  # (B, S, d)
    else:
        pos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        x, caches, _ = lm_hidden(
            params, tokens, cfg, positions=pos, caches=caches, kv_len=lens,
            page_table=page_table, scale_base=scale_base, causal=True)
    return x, caches


def lm_prefill_paged(params, batch, caches, page_table, cfg):
    """Batched prefill into the paged cache.

    batch: tokens (B, S) right-padded prompt SUFFIXES, lens (B,) TOTAL
    valid lengths (lens == 0 marks an inactive slot whose page-table row
    must point at the trash page), and optional offsets (B,) — each
    slot's first computed position.  A nonzero offset means positions
    [0, offset) live in already-written pages — a copy-on-write shared
    prefix, or (continuous batching) this slot's OWN earlier prefill
    chunks: the slot's tokens are the suffix starting at ``offset``,
    attending through the page table to the earlier rows.  Optional
    ``scale_base`` (B,) separates the per-slot running-statistics origin
    from the chunk offset: positions >= scale_base were computed by THIS
    slot (they count toward camformer's k_scale running mean across
    chunks), positions below it live in another slot's shared pages.  It
    defaults to ``offsets`` (single-dispatch prefill, where the two
    coincide).  With cfg.prefill_chunk set and S a chunk multiple, the
    suffix batch is processed in chunks that attend to the pages written
    so far (chunked prefill, activation memory bounded by the chunk).
    Returns (per-slot last-suffix-token logits (B, V), pools).
    """
    lens = batch["lens"].astype(jnp.int32)
    offsets = batch.get("offsets")
    offsets = (jnp.zeros(lens.shape, jnp.int32) if offsets is None
               else offsets.astype(jnp.int32))
    x, caches = _paged_suffix_hidden(params, batch, caches, page_table, cfg)
    # the final valid token sits at suffix row (lens - offsets - 1)
    last = jnp.take_along_axis(
        x, jnp.clip(lens - offsets - 1, 0, x.shape[1] - 1)[
            :, None, None].astype(jnp.int32),
        axis=1)[:, 0]
    return _head_logits(params, last, cfg), caches


def lm_verify_paged(params, batch, caches, page_table, cfg):
    """Speculative-decode verification over the paged Sq>1 seam.

    Identical contract to ``lm_prefill_paged`` but returns the logits of
    EVERY suffix position — (B, S, V) — so the engine can score all k+1
    speculative positions in one fused step (row j holds the target
    distribution for the token AFTER input position offsets + j).

    The pass runs under ``spec_verify`` semantics: stateful backends use
    per-query running ``k_scale`` (each chunk column sees exactly the
    scale the sequential loop would have used at its position) and stash
    the chunk's key means for exact rollback.  The chunk is k+1 tokens,
    so it never needs ``prefill_chunk`` slicing.
    """
    cfg = cfg.replace(spec_verify=True, prefill_chunk=0)
    x, caches = _paged_suffix_hidden(params, batch, caches, page_table, cfg)
    return _all_logits(params, x, cfg), caches


def lm_decode_paged(params, tokens, pos, kv_len, caches, page_table, cfg,
                    base=None):
    """One decode step against the paged cache. tokens/pos/kv_len: (B,);
    base: (B,) per-slot prefix-sharing offset (see lm_prefill_paged)."""
    b = tokens.shape[0]
    positions = pos.reshape(b, 1).astype(jnp.int32)
    x, caches, _ = lm_hidden(
        params, tokens.reshape(b, 1), cfg, positions=positions,
        caches=caches, kv_len=kv_len.astype(jnp.int32),
        page_table=page_table, scale_base=base, causal=True)
    return _last_logits(params, x, cfg), caches


def lm_decode(params, tokens, pos, kv_len, caches, cfg):
    """One decode step. tokens (B,), pos (B,), kv_len (B,).

    Returns (logits (B, V), updated caches)."""
    b = tokens.shape[0]
    positions = pos.reshape(b, 1).astype(jnp.int32)
    x, caches, _ = lm_hidden(
        params, tokens.reshape(b, 1), cfg, positions=positions,
        caches=caches, cache_index=pos.astype(jnp.int32),
        kv_len=kv_len.astype(jnp.int32), causal=True)
    logits = _last_logits(params, x, cfg)
    return logits, caches


def _last_logits(params, x, cfg):
    return _head_logits(params, x[:, -1], cfg)


@jax.named_scope("lm_head")
def _all_logits(params, x, cfg):
    """Vocabulary logits for every position of x (B, S, d) -> (B, S, V)."""
    dt = x.dtype
    if cfg.tie_embeddings:
        head = params["embed"]["tok"].astype(dt).T
    else:
        head = params["embed"]["head"].astype(dt)
    logits = x @ head
    logits = constrain(logits, ("batch", None, "vocab")).astype(jnp.float32)
    if logits.shape[-1] > cfg.vocab:  # vocab-padding columns never sampled
        logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab,
                           logits, -1e9)
    return logits


@jax.named_scope("lm_head")
def _head_logits(params, last, cfg):
    """Vocabulary logits for per-slot final hidden states last (B, d)."""
    dt = last.dtype
    if cfg.tie_embeddings:
        head = params["embed"]["tok"].astype(dt).T
    else:
        head = params["embed"]["head"].astype(dt)
    logits = last @ head
    logits = constrain(logits, ("batch", "vocab")).astype(jnp.float32)
    if logits.shape[-1] > cfg.vocab:  # vocab-padding columns never sampled
        logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab,
                           logits, -1e9)
    return logits


def make_model_def():
    return ModelDef(
        specs=lm_specs,
        loss=lm_loss,
        prefill=lm_prefill,
        decode=lm_decode,
        cache_specs=lm_cache_specs,
        page_specs=lm_page_specs,
        prefill_paged=lm_prefill_paged,
        decode_paged=lm_decode_paged,
        verify_paged=lm_verify_paged,
    )
