"""Pluggable attention backends: one protocol for dense / binary / CAM.

The paper's thesis is that attention is an associative-memory *operation*
with interchangeable physical realizations (BA-CAM voltage-domain search
vs. digital arithmetic, Sec. III).  This module is that seam in code: an
``AttentionBackend`` defines how one attention layer realizes

  * its **contiguous KV cache** layout (``cache_spec``) and the decode
    step against it (``decode``),
  * its **paged KV pool** layout (``page_spec``) and the paged
    prefill/decode step against it (``paged_decode`` — the single
    serving-engine path),
  * plain attention over freshly computed K/V (``prefill`` — training,
    whole-prompt prefill, and cross-attention).

Concrete backends (registered at import, mirroring ``models/registry.py``):

  * ``dense``     — standard softmax attention; bf16 K/V caches & pages.
  * ``binary``    — HAD-binarized scoring, full softmax; dense storage
                    (keys are binarized at attend time, the ablation
                    ladder's single-stage upper bound).  Paged pools add
                    a running per-slot ``k_scale`` so the HAD softmax
                    temperature streams (no gathered-key recompute).
  * ``camformer`` — the paper: bit-packed binary Key SRAM (6.25% of bf16),
                    two-stage top-k CAM search, sparse top-k V gather;
                    fused Pallas kernels on the decode hot paths.

Every backend's ``paged_decode`` has TWO selectable realizations
(``ModelConfig.paged_impl``): ``"fused"`` (default) runs the decode row
through a Pallas paged kernel — the flash-decode skeleton
(kernels/paged_flash_decode.py) for dense/binary, the CAM search kernel
(kernels/bacam_decode.py) for camformer — walking the slot's page list
via scalar-prefetched page-table rows with a streaming softmax, so
decode reads are proportional to LIVE pages; ``"gather"`` keeps the XLA
page-gather + masked attend as the reference oracle every kernel claim
is pinned against (``kernels/ref.paged_gather_ref``).  Sq > 1 chunk
rows (chunked prefill and speculative verify) run the SAME flash
skeleton with per-row causal anchors for dense/binary
(``ModelConfig.prefill_impl``: "auto" follows paged_impl); camformer
chunks still gather — there is no fused Sq>1 CAM kernel yet.  The
``hybrid`` backend closes that gap structurally: flash-scored fused
prefill chunks over a dense key pool + CAM paged decode.

Per-layer policy lives on ``ModelConfig`` (``attn_backend`` +
``layer_backends``; ``cfg.backend_for(layer)`` resolves a name) so hybrid
models can run, e.g., sliding-window layers on ``dense`` and
full-attention layers on ``camformer`` — the mixed-tile regime of
X-Former-style accelerators.  New realizations are a ``register_backend``
call, not another ``if``-ladder site.

Fused-step contract (the overlapped serving engine): ``paged_decode`` is
dispatched once per engine tick for EVERY batch row inside one jit —
decode rows, chunked-prefill rows, and inert rows alike — with sampling
fused behind it, so the sampled token ids are the tick's only
host<->device readback.  That imposes two row-level requirements on
every backend:

  * rows are independent: one row's inputs never change another row's
    outputs or cache state (attention is per-row by construction; the
    only known coupling is MoE capacity routing, which serving configs
    must treat as approximate under overlap);
  * ``kv_len == 0`` marks an INERT row: its page writes must resolve to
    the trash page and its per-slot running statistics (camformer's
    ``k_scale``) must be left untouched, so the engine can carry
    preempted/finished/mid-prefill slots through a tick without
    corrupting them.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import bacam
from repro.core.attention import (AttentionSpec, attention,
                                  binary_paged_attention,
                                  camformer_paged_attention,
                                  topk_softmax_weights)
from repro.core.binarize import sign_pm1
from repro.core.topk import NEG_INF, two_stage_topk

__all__ = [
    "AttentionBackend", "DenseBackend", "BinaryBackend", "CamformerBackend",
    "HybridBackend",
    "register_backend", "get_backend", "list_backends", "backends_for",
]


# ---------------------------------------------------------------------------
# registry

_BACKENDS: Dict[str, "AttentionBackend"] = {}


def register_backend(backend: "AttentionBackend") -> "AttentionBackend":
    """Register a backend instance under ``backend.name`` (last wins)."""
    _BACKENDS[backend.name] = backend
    return backend


def get_backend(name: str) -> "AttentionBackend":
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown attention backend {name!r}; have {sorted(_BACKENDS)}")


def list_backends() -> list:
    return sorted(_BACKENDS)


def backends_for(cfg) -> tuple:
    """Resolve the per-layer backend objects for a model config."""
    return tuple(get_backend(cfg.backend_for(i)) for i in range(cfg.n_layers))


# ---------------------------------------------------------------------------
# shared cache plumbing


def _seq_insert(buf, upd, index):
    """Insert `upd` into `buf` along axis 2 (cache seq).

    index: scalar — uniform write (train/prefill/dry-run decode);
           (B,) array — ragged per-slot write (continuous batching).
    """
    zero = jnp.zeros((), jnp.int32)
    if jnp.ndim(index) == 0:
        return jax.lax.dynamic_update_slice(buf, upd, (zero, zero, index, zero))
    one = lambda b, u, i: jax.lax.dynamic_update_slice(b, u, (zero, i, zero))
    return jax.vmap(one)(buf, upd, index.astype(jnp.int32))


_TRASH_PAGE = 0  # serving/kv_cache.py contract: physical page 0 is trash


def _running_k_scale(k_scale, k, pos, kv_len, base):
    """Update a slot's running per-head key scale over VALID tokens only.

    k_scale: (B, H_kv) stored running mean of mean_d(|k|); k: (B, H_kv,
    S, D) the freshly written keys; pos: (B, S) their logical positions;
    kv_len: (B,) valid tokens INCLUDING this write; base: (B,) or None —
    the prefix-sharing offset below which positions live in ANOTHER
    slot's shared pages (they never counted toward this slot's mean).
    Rows with no valid tokens (kv_len == 0 inert rows, fully-padded
    chunks) leave the stored scale untouched — the fused-step contract.
    """
    b = k.shape[0]
    valid = (pos < kv_len[:, None]).astype(jnp.float32)  # (B, S)
    mean_d = jnp.mean(jnp.abs(k.astype(jnp.float32)), axis=3)  # (B,Hkv,S)
    new_sum = jnp.einsum("bhs,bs->bh", mean_d, valid)
    cnt = jnp.sum(valid, axis=-1)  # (B,)
    if base is None:
        base = jnp.zeros((b,), jnp.int32)
    prior = jnp.clip(jnp.minimum(pos[:, 0], kv_len)
                     - base.reshape(b).astype(jnp.int32),
                     0, None).astype(jnp.float32)
    total = prior + cnt
    ks = ((k_scale * prior[:, None] + new_sum)
          / jnp.maximum(total, 1.0)[:, None])
    return jnp.where((total > 0)[:, None], ks, k_scale)


def _chunk_scale_seq(k_scale, k, pos, kv_len, base):
    """Per-query running key scales for a speculative verify chunk.

    The stored ``k_scale`` is one value per slot, which is correct for a
    single-token decode step but not for an Sq>1 verify chunk: the query
    at chunk column j must see the running mean over keys up to ITS OWN
    position (``[base, pos+j]``) — the value the sequential decode loop
    would have used — not a mean contaminated by the chunk's later keys.

    Returns ``(per_query (B, H_kv, S), means (B, H_kv, S))`` — the
    sequential-semantics scale per query column, and the chunk's
    per-position valid-masked ``mean_d(|k|)`` (stashed in the ``k_means``
    pool leaf so the host-planned rollback can rebuild the running mean
    at ANY accepted length exactly; see serving/speculate.py).
    """
    b = k.shape[0]
    valid = (pos < kv_len[:, None]).astype(jnp.float32)  # (B, S)
    means = (jnp.mean(jnp.abs(k.astype(jnp.float32)), axis=3)
             * valid[:, None, :])  # (B, Hkv, S)
    if base is None:
        base = jnp.zeros((b,), jnp.int32)
    prior = jnp.clip(jnp.minimum(pos[:, 0], kv_len)
                     - base.reshape(b).astype(jnp.int32),
                     0, None).astype(jnp.float32)  # (B,)
    cum = jnp.cumsum(means, axis=2)
    cnt = prior[:, None] + jnp.cumsum(valid, axis=1)  # (B, S)
    per_q = ((k_scale[:, :, None] * prior[:, None, None] + cum)
             / jnp.maximum(cnt, 1.0)[:, None, :])
    per_q = jnp.where((cnt > 0)[:, None, :], per_q, k_scale[:, :, None])
    return per_q, means


def _page_phys_rows(page_table, positions, page: int, kv_len=None):
    """(physical page, in-page row) of each logical position. Both (B, S).

    With kv_len (B,), positions >= kv_len (right-padding rows of a
    batched prefill) resolve to the TRASH page: their logical positions
    can exceed the slot's page-table extent (prefix-sharing offsets push
    padding past max_len), where jnp's clamped indexing would otherwise
    alias them onto the slot's last page and corrupt live rows.
    """
    b = positions.shape[0]
    pos = positions.astype(jnp.int32)
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
    col = jnp.clip(pos // page, 0, page_table.shape[1] - 1)
    phys = page_table[bidx, col]
    if kv_len is not None:
        phys = jnp.where(pos < kv_len.reshape(b, 1), phys, _TRASH_PAGE)
    return phys, pos % page


# ---------------------------------------------------------------------------
# protocol


class AttentionBackend:
    """One physical realization of the attention operation.

    Subclasses set ``name``/``mode`` and implement the five protocol
    methods (``cache_spec``, ``page_spec``, ``prefill``, ``decode``,
    ``paged_decode``) plus the ``write_cache`` splice used by the
    contiguous prefill path.  All array arguments follow the
    ``core/attention`` conventions: q (B, H, Sq, D), k/v (B, H_kv, S, D),
    GQA never materializes repeated KV.
    """

    name: str = "?"
    mode: str = "?"  # core/attention AttentionSpec operator mode

    # -- operator spec --------------------------------------------------
    def spec(self, cfg) -> AttentionSpec:
        return AttentionSpec(
            mode=self.mode,
            k_top=cfg.k_top,
            group_size=cfg.group_size,
            stage1_k=cfg.stage1_k,
            use_kernel=cfg.use_kernel,
        )

    # -- layouts --------------------------------------------------------
    def cache_spec(self, cfg, batch: int, cache_len: int, dtype) -> dict:
        """{leaf: (ShapeDtypeStruct, logical axes)} for one layer's
        contiguous self-attention cache."""
        raise NotImplementedError

    def page_spec(self, cfg, n_pages: int, page_size: int, max_batch: int,
                  dtype) -> dict:
        """{leaf: (ShapeDtypeStruct, logical axes)} for one layer's PAGED
        pool (serving/kv_cache.py page-table geometry)."""
        raise NotImplementedError

    def cache_bytes_per_token(self, cfg, dtype) -> float:
        """KV bytes appended per token per layer (capacity accounting)."""
        raise NotImplementedError

    # -- attention ------------------------------------------------------
    def prefill(self, q, k, v, cfg, *, causal=True, positions=None,
                window=None):
        """Attention over freshly computed K/V (train / whole-prompt
        prefill / cross-attention).  Returns (B, H, Sq, Dv)."""
        return attention(q, k, v, self.spec(cfg), causal=causal,
                         q_positions=positions, window=window)

    def decode(self, q, cache, k, v, cache_index, kv_len, positions, cfg, *,
               kv_positions=None, window=None):
        """Write k/v at ``cache_index`` then attend against the contiguous
        cache.  Returns (out, new_cache)."""
        raise NotImplementedError

    def paged_decode(self, q, cache, k, v, positions, page_table, kv_len,
                     cfg, *, base=None):
        """Splice k/v into the paged pools at their logical positions and
        attend through the page table (decode rows AND chunked-prefill
        rows — the single serving path).  Returns (out, new_pools).

        ``base`` (B,) is each slot's prefix-sharing offset: positions
        below it were prefilled by ANOTHER slot into shared pages, so
        per-slot running statistics (camformer's ``k_scale``) must count
        only positions >= base.  None means no sharing (all zeros).

        Fused-step entry (module docstring): called for every batch row
        of every tick inside one jit.  Rows with ``kv_len == 0`` are
        INERT — implementations must route their writes to the trash
        page (``_page_phys_rows`` does this when given kv_len) and leave
        their per-slot statistics untouched; their attention output is
        unspecified and never read.
        """
        raise NotImplementedError

    # -- analytic decode-step I/O accounting ----------------------------
    def paged_io_stats(self, cfg, dtype, *, kv_len: int, page_size: int,
                       n_table_pages: int) -> dict:
        """Analytic per-layer, per-slot decode-step I/O in bytes.

        ``fused_read_bytes``/``gather_read_bytes``: KV pool bytes READ
        per decode token by each ``paged_impl`` realization (fused walks
        only the slot's LIVE pages; gather dereferences the full
        ``n_table_pages`` table extent).  ``gather_scratch_bytes``: the
        peak logical-order scratch the gather impl materializes per slot
        (the fused kernels stream page tiles — zero scratch).  Benchmarks
        multiply by ``n_layers`` / batch for the system-level numbers.

        ``prefill_fused_read_bytes``/``prefill_gather_read_bytes``: the
        same accounting for one Sq > 1 CHUNK attend (chunked prefill /
        speculative verify) under each ``prefill_impl`` realization —
        the chunk reads the pools once regardless of chunk length, so
        bytes per prefill TOKEN divide by the chunk size.
        """
        hkv, d = cfg.n_kv_heads, cfg.head_dim
        item = jnp.dtype(dtype).itemsize
        row = 2 * hkv * d * item  # one K row + one V row, all kv heads
        live_rows = -(-max(kv_len, 1) // page_size) * page_size
        table_rows = n_table_pages * page_size
        return {
            "fused_read_bytes": live_rows * row,
            "gather_read_bytes": table_rows * row,
            "gather_scratch_bytes": table_rows * row,
            "prefill_fused_read_bytes": live_rows * row,
            "prefill_gather_read_bytes": table_rows * row,
        }

    # -- contiguous-cache write (shared ring-buffer clamp) --------------
    def write_cache(self, cache, k, v, index, cfg):
        """Insert new K/V at `index` (traced) along the cache seq axis.

        If the update is longer than the cache (window ring-buffer
        prefill), only the trailing cache-length slice is stored at 0.
        """
        if cache is None:
            return None
        cache_len = cache["v"].shape[2]
        if k.shape[2] > cache_len:
            k, v = k[:, :, -cache_len:], v[:, :, -cache_len:]
            index = jnp.int32(0)
        return self._write(cache, k, v, index, cfg)

    def _write(self, cache, k, v, index, cfg):
        raise NotImplementedError


# ---------------------------------------------------------------------------
# dense


class DenseBackend(AttentionBackend):
    """Standard softmax attention over full-precision K/V (the oracle)."""

    name = "dense"
    mode = "dense"

    def cache_spec(self, cfg, batch, cache_len, dtype):
        hkv, d = cfg.n_kv_heads, cfg.head_dim
        return {
            "k": (jax.ShapeDtypeStruct((batch, hkv, cache_len, d), dtype),
                  ("batch", "kv_heads", "kv_seq", "head_dim")),
            "v": (jax.ShapeDtypeStruct((batch, hkv, cache_len, d), dtype),
                  ("batch", "kv_heads", "kv_seq", "head_dim")),
        }

    def page_spec(self, cfg, n_pages, page_size, max_batch, dtype):
        hkv, d = cfg.n_kv_heads, cfg.head_dim
        return {
            "k_pages": (jax.ShapeDtypeStruct(
                (n_pages, hkv, page_size, d), dtype),
                (None, "kv_heads", None, "head_dim")),
            "v_pages": (jax.ShapeDtypeStruct(
                (n_pages, hkv, page_size, d), dtype),
                (None, "kv_heads", None, "head_dim")),
        }

    def cache_bytes_per_token(self, cfg, dtype):
        d = cfg.head_dim
        return 2 * cfg.n_kv_heads * d * jnp.dtype(dtype).itemsize

    def _write(self, cache, k, v, index, cfg):
        return {"k": _seq_insert(cache["k"], k.astype(cache["k"].dtype), index),
                "v": _seq_insert(cache["v"], v.astype(cache["v"].dtype), index)}

    def decode(self, q, cache, k, v, cache_index, kv_len, positions, cfg, *,
               kv_positions=None, window=None):
        new_cache = self.write_cache(cache, k, v, cache_index, cfg)
        ck, cv = new_cache["k"], new_cache["v"]
        kv_pos = (jnp.arange(ck.shape[2], dtype=jnp.int32)[None]
                  if kv_positions is None else kv_positions)
        kv_valid = kv_pos < kv_len.reshape(-1, 1)
        out = attention(
            q, ck, cv, self.spec(cfg), causal=True,
            q_positions=positions, kv_positions=kv_pos,
            kv_valid=kv_valid, window=window or cfg.window)
        return out, new_cache

    def _paged_write(self, cache, k, v, positions, page_table, kv_len=None):
        page = cache["k_pages"].shape[2]
        phys, row = _page_phys_rows(page_table, positions, page, kv_len)
        new_k = cache["k_pages"].at[phys, :, row].set(
            k.astype(cache["k_pages"].dtype).transpose(0, 2, 1, 3))
        new_v = cache["v_pages"].at[phys, :, row].set(
            v.astype(cache["v_pages"].dtype).transpose(0, 2, 1, 3))
        return {"k_pages": new_k, "v_pages": new_v}

    def paged_decode(self, q, cache, k, v, positions, page_table, kv_len,
                     cfg, *, base=None):
        # dense pages carry no per-slot running statistics: `base` only
        # affects which positions are freshly written, which the page
        # table already encodes
        new_cache = self._paged_write(cache, k, v, positions, page_table,
                                      kv_len)
        out = self._paged_attend(q, new_cache, positions, page_table,
                                 kv_len, cfg)
        return out, new_cache

    def _paged_attend(self, q, cache, positions, page_table, kv_len, cfg):
        sq = q.shape[2]
        impl = cfg.paged_impl if sq == 1 else cfg.prefill_paged_impl
        if impl == "fused":
            # Fused paged flash kernel (kernels/paged_flash_decode.py):
            # page-table walk with an online softmax — bytes
            # proportional to live pages, no logical-order gather.
            # Sq > 1 chunk rows (chunked prefill / speculative verify)
            # run the same skeleton with per-row causal anchors keyed
            # on the chunk's first position (the slot's offsets).
            from repro.kernels import ops as kops

            if sq == 1:
                return kops.paged_flash_decode(
                    q, cache["k_pages"], cache["v_pages"], page_table,
                    kv_len.reshape(-1), positions[:, 0], window=cfg.window)
            return kops.paged_flash_prefill(
                q, cache["k_pages"], cache["v_pages"], page_table,
                kv_len.reshape(-1), positions[:, 0], window=cfg.window)
        from repro.kernels.ref import paged_gather_ref

        # Reference impl: gather the slot's pages into logical order
        # and run the standard masked attend — logical position p is
        # row p of the gather, so the contiguous-cache masking applies
        # verbatim.
        ck = paged_gather_ref(cache["k_pages"], page_table)
        cv = paged_gather_ref(cache["v_pages"], page_table)
        kv_pos = jnp.arange(ck.shape[2], dtype=jnp.int32)[None]
        kv_valid = kv_pos < kv_len.reshape(-1, 1)
        return attention(
            q, ck, cv, self.spec(cfg), causal=True,
            q_positions=positions, kv_positions=kv_pos,
            kv_valid=kv_valid, window=cfg.window)


class BinaryBackend(DenseBackend):
    """HAD-binarized scoring with a FULL softmax (no top-k sparsity).

    Contiguous storage is identical to dense (keys binarize at attend
    time); only the scoring arithmetic changes — the single-stage upper
    bound of the Tables III/IV ablation ladder.

    The PAGED pools additionally carry camformer's running per-slot
    ``k_scale`` (HAD softmax-temperature bookkeeping, maintained at
    page-write time over valid tokens only), which makes the paged path
    genuinely binarized: before, ``paged_decode`` inherited the dense
    gather + full-precision softmax wholesale, so the "binary" serving
    lane measured gather cost rather than sign-match scoring — and a
    streaming kernel could not reproduce the old temperature anyway
    (a mean over ALL gathered rows, trash-page garbage included).
    """

    name = "binary"
    mode = "binary"

    def page_spec(self, cfg, n_pages, page_size, max_batch, dtype):
        spec = super().page_spec(cfg, n_pages, page_size, max_batch, dtype)
        spec["k_scale"] = (
            jax.ShapeDtypeStruct((max_batch, cfg.n_kv_heads), jnp.float32),
            ("batch", "kv_heads"))
        if cfg.spec_k > 0:
            # speculative verify scratch: the tick's per-position key
            # means, so the accept-prefix rollback can rebuild the
            # running k_scale at the accepted length exactly
            spec["k_means"] = (
                jax.ShapeDtypeStruct(
                    (max_batch, cfg.n_kv_heads, cfg.spec_k + 1),
                    jnp.float32),
                ("batch", "kv_heads", None))
        return spec

    def _paged_write(self, cache, k, v, positions, page_table, kv_len=None,
                     base=None):
        pages = super()._paged_write(cache, k, v, positions, page_table,
                                     kv_len)
        b = k.shape[0]
        pos = positions.astype(jnp.int32)
        kvl = (jnp.full((b,), pos.shape[1], jnp.int32) if kv_len is None
               else kv_len.reshape(b).astype(jnp.int32))
        pages["k_scale"] = _running_k_scale(
            cache["k_scale"], k, pos, kvl, base)
        if "k_means" in cache:
            pages["k_means"] = cache["k_means"]
        return pages

    def paged_decode(self, q, cache, k, v, positions, page_table, kv_len,
                     cfg, *, base=None):
        new_cache = self._paged_write(cache, k, v, positions, page_table,
                                      kv_len, base=base)
        k_scale = new_cache["k_scale"]
        if (q.shape[2] > 1 and cfg.spec_verify and "k_means" in new_cache
                and new_cache["k_means"].shape[-1] == q.shape[2]):
            # speculative verify chunk: sequential-semantics per-query
            # scales, and stash the chunk means for exact rollback
            k_scale, means = _chunk_scale_seq(
                cache["k_scale"], k, positions.astype(jnp.int32),
                kv_len.reshape(k.shape[0]).astype(jnp.int32), base)
            new_cache["k_means"] = means
        # decode rows follow paged_impl; Sq > 1 chunk rows (prefill /
        # verify — the per-query scales above fold into the kernel's
        # temperature operand) follow the effective prefill impl
        impl = cfg.paged_impl if q.shape[2] == 1 else cfg.prefill_paged_impl
        out = binary_paged_attention(
            q, new_cache["k_pages"], new_cache["v_pages"],
            k_scale, page_table, kv_len, positions,
            self.spec(cfg), window=cfg.window, impl=impl)
        return out, new_cache


# ---------------------------------------------------------------------------
# camformer


class CamformerBackend(AttentionBackend):
    """The paper's BA-CAM realization: bit-packed binary Key SRAM,
    two-stage top-k CAM search, softmax over the k survivors, sparse
    top-k V gather; fused Pallas kernels on the decode hot paths."""

    name = "camformer"
    mode = "camformer"

    def cache_spec(self, cfg, batch, cache_len, dtype):
        hkv, d = cfg.n_kv_heads, cfg.head_dim
        return {
            "k_packed": (jax.ShapeDtypeStruct(
                (batch, hkv, cache_len, d // 32), jnp.uint32),
                ("batch", "kv_heads", "kv_seq", None)),
            "v": (jax.ShapeDtypeStruct((batch, hkv, cache_len, d), dtype),
                  ("batch", "kv_heads", "kv_seq", "head_dim")),
            "k_scale": (jax.ShapeDtypeStruct((batch, hkv), jnp.float32),
                        ("batch", "kv_heads")),
        }

    def page_spec(self, cfg, n_pages, page_size, max_batch, dtype):
        hkv, d = cfg.n_kv_heads, cfg.head_dim
        if page_size % cfg.group_size != 0:
            raise ValueError(
                f"page_size={page_size} must tile by "
                f"group_size={cfg.group_size}")
        spec = {
            "kp_pages": (jax.ShapeDtypeStruct(
                (n_pages, hkv, page_size, d // 32), jnp.uint32),
                (None, "kv_heads", None, None)),
            "v_pages": (jax.ShapeDtypeStruct(
                (n_pages, hkv, page_size, d), dtype),
                (None, "kv_heads", None, "head_dim")),
            "k_scale": (jax.ShapeDtypeStruct((max_batch, hkv), jnp.float32),
                        ("batch", "kv_heads")),
        }
        if cfg.spec_k > 0:
            # speculative verify scratch (see BinaryBackend.page_spec) —
            # doubly necessary here: the packed pool stores signs only,
            # so chunk key magnitudes are unrecoverable after the write
            spec["k_means"] = (
                jax.ShapeDtypeStruct(
                    (max_batch, hkv, cfg.spec_k + 1), jnp.float32),
                ("batch", "kv_heads", None))
        return spec

    def cache_bytes_per_token(self, cfg, dtype):
        d = cfg.head_dim
        return cfg.n_kv_heads * (d // 8 + d * jnp.dtype(dtype).itemsize)

    def _write(self, cache, k, v, index, cfg):
        kp = bacam.pack_bits(sign_pm1(k))
        new_kp = _seq_insert(cache["k_packed"], kp, index)
        new_v = _seq_insert(cache["v"], v.astype(cache["v"].dtype), index)
        # running per-head key scale (softmax temperature bookkeeping)
        step = jnp.float32(k.shape[2])
        new_mean = jnp.mean(jnp.abs(k.astype(jnp.float32)), axis=(2, 3))
        idx_f = jnp.reshape(index.astype(jnp.float32), (-1, 1))
        total = idx_f + step
        k_scale = (cache["k_scale"] * idx_f + new_mean * step) / total
        return {"k_packed": new_kp, "v": new_v, "k_scale": k_scale}

    def decode(self, q, cache, k, v, cache_index, kv_len, positions, cfg, *,
               kv_positions=None, window=None):
        new_cache = self.write_cache(cache, k, v, cache_index, cfg)
        # distributed CAM search targets the batch=1 long-context regime
        # where the cache sequence takes every mesh axis; batched decode
        # keeps batch-sharded local search instead
        if cfg.distributed_topk and kv_positions is None and q.shape[0] == 1:
            out = self._distributed_attend(
                q, new_cache, kv_len, positions, cfg)
        else:
            out = self._cache_attend(
                q, new_cache, kv_len, positions, cfg,
                kv_positions=kv_positions)
        return out, new_cache

    def paged_decode(self, q, cache, k, v, positions, page_table, kv_len,
                     cfg, *, base=None):
        new_cache = self._paged_write(
            cache, k, v, positions, page_table, kv_len, cfg, base=base)
        k_scale = new_cache["k_scale"]
        if (q.shape[2] > 1 and cfg.spec_verify and "k_means" in new_cache
                and new_cache["k_means"].shape[-1] == q.shape[2]):
            # speculative verify chunk (see BinaryBackend.paged_decode)
            k_scale, means = _chunk_scale_seq(
                cache["k_scale"], k, positions.astype(jnp.int32),
                kv_len.reshape(k.shape[0]).astype(jnp.int32), base)
            new_cache["k_means"] = means
        out = camformer_paged_attention(
            q, new_cache["kp_pages"], new_cache["v_pages"],
            k_scale, page_table, kv_len, positions,
            self.spec(cfg), window=cfg.window, impl=cfg.paged_impl)
        return out, new_cache

    def paged_io_stats(self, cfg, dtype, *, kv_len, page_size,
                       n_table_pages):
        hkv, d = cfg.n_kv_heads, cfg.head_dim
        item = jnp.dtype(dtype).itemsize
        kp_row = hkv * (d // 8)  # bit-packed keys: 1 bit/element
        live_rows = -(-max(kv_len, 1) // page_size) * page_size
        table_rows = n_table_pages * page_size
        # V is never gathered: only the k_top survivors are read, per
        # GQA query row (worst case all-unique selections).
        g = cfg.n_heads // hkv
        v_sel = hkv * g * min(cfg.k_top, kv_len or 1) * d * item
        return {
            "fused_read_bytes": live_rows * kp_row + v_sel,
            "gather_read_bytes": table_rows * kp_row + v_sel,
            "gather_scratch_bytes": table_rows * kp_row,
            # no fused Sq>1 CAM kernel yet (ROADMAP stretch): chunk
            # attends gather the packed pool under either prefill_impl
            "prefill_fused_read_bytes": table_rows * kp_row + v_sel,
            "prefill_gather_read_bytes": table_rows * kp_row + v_sel,
        }

    # -- internals ------------------------------------------------------
    def _paged_write(self, cache, k, v, positions, page_table, kv_len, cfg,
                     base=None):
        """Splice new K/V into the paged pools at their logical positions.

        k, v: (B, H_kv, S, D); positions: (B, S) logical token positions;
        kv_len: (B,) — valid tokens per slot INCLUDING this write
        (prefill: the true prompt length; decode: pos + 1).  Tokens at
        positions >= kv_len are right-padding: their page-table entries
        resolve to the trash page and they are excluded from the k_scale
        running mean.

        base: (B,) prefix-sharing offset.  The slot's k_scale running
        mean counts only the positions THIS slot computed (>= base) —
        tokens below base live in shared pages written by another slot,
        whose k contribution this slot never sees.  The suffix mean is
        the sharing approximation for the softmax temperature; it keeps
        k_scale strictly per-slot state (fork siblings stay independent).
        """
        page = cache["kp_pages"].shape[2]
        b = k.shape[0]
        pos = positions.astype(jnp.int32)
        kv_len = kv_len.reshape(b).astype(jnp.int32)
        phys, row = _page_phys_rows(page_table, pos, page, kv_len)

        kp = bacam.pack_bits(sign_pm1(k))  # (B, H_kv, S, W)
        new_kp = cache["kp_pages"].at[phys, :, row].set(
            kp.transpose(0, 2, 1, 3))
        new_v = cache["v_pages"].at[phys, :, row].set(
            v.astype(cache["v_pages"].dtype).transpose(0, 2, 1, 3))

        ks = _running_k_scale(cache["k_scale"], k, pos, kv_len, base)
        pages = {"kp_pages": new_kp, "v_pages": new_v, "k_scale": ks}
        if "k_means" in cache:
            pages["k_means"] = cache["k_means"]
        return pages

    def _cache_attend(self, q, cache, kv_len, positions, cfg,
                      kv_positions=None):
        """Decode/serve attention against the packed binary cache."""
        spec = self.spec(cfg)
        b, h, sq, d = q.shape
        hkv = cfg.n_kv_heads
        g = h // hkv
        skv = cache["v"].shape[2]
        qb = sign_pm1(q.astype(jnp.float32))
        q_scale = jnp.mean(jnp.abs(q.astype(jnp.float32)), axis=-1)  # (B,H,Sq)

        qp = bacam.pack_bits(qb).reshape(b * hkv, g * sq, d // 32)
        kp = cache["k_packed"].reshape(b * hkv, skv, d // 32)
        if spec.use_kernel and kv_positions is not None:
            # the fused kernel masks from slot order; ring caches with
            # rotated positions take the jnp path instead
            spec = spec.replace(use_kernel=False)
        if spec.use_kernel:
            from repro.kernels import ops as kops

            pos = jnp.broadcast_to(
                positions[:, None, :], (b, hkv, g * sq)).reshape(
                b * hkv, g * sq)
            kvl = jnp.broadcast_to(
                kv_len.reshape(b, 1), (b, hkv)).reshape(b * hkv)
            cand_v, cand_i = kops.bacam_attention_scores_topk_packed(
                qp, kp, pos, kvl, d=d,
                group=spec.group_size, stage1_k=spec.stage1_k,
                causal=True, window=cfg.window)
            top_v, sel = jax.lax.top_k(
                cand_v, min(spec.k_top, cand_v.shape[-1]))
            top_i = jnp.take_along_axis(cand_i, sel, axis=-1)
            top_v = top_v.reshape(b, hkv, g, sq, -1)
            top_i = top_i.reshape(b, hkv, g, sq, -1)
        else:
            scores = bacam.hamming_scores_packed(
                qp.reshape(b, hkv, g * sq, d // 32),
                kp.reshape(b, hkv, skv, d // 32),
                d,
            )  # (B,Hkv,G*Sq,Skv)
            if kv_positions is None:
                kpos = jnp.arange(skv, dtype=jnp.int32)[None, None, None]
            else:  # ring cache: slots hold true (rotated) positions
                kpos = kv_positions[:, None, None, :]
            qpos = jnp.broadcast_to(positions[:, None, :], (b, hkv, sq))
            qpos = jnp.broadcast_to(
                qpos[:, :, None, :], (b, hkv, g, sq)).reshape(
                b, hkv, g * sq)[..., None]
            ok = kpos < kv_len.reshape(b, 1, 1, 1)
            ok = ok & (kpos <= qpos)
            if cfg.window is not None:
                ok = ok & (kpos > qpos - cfg.window)
            masked = jnp.where(ok, scores.astype(jnp.float32), NEG_INF)
            top_v, top_i = two_stage_topk(
                masked, k=spec.k_top, group_size=spec.group_size,
                stage1_k=spec.stage1_k)
            top_v = top_v.reshape(b, hkv, g, sq, -1)
            top_i = top_i.reshape(b, hkv, g, sq, -1)

        scale = 1.0 / (d**0.5)
        temp = (q_scale.reshape(b, hkv, g, sq)[..., None]
                * cache["k_scale"][:, :, None, None, None])
        w, _ = topk_softmax_weights(top_v, temp, scale)
        v_exp = cache["v"][:, :, None, None]  # (B,Hkv,1,1,Skv,Dv)
        v_sel = jnp.take_along_axis(v_exp, top_i[..., None], axis=-2)
        out = jnp.einsum(
            "bhgqk,bhgqkd->bhgqd", w.astype(cache["v"].dtype), v_sel)
        return out.reshape(b, h, sq, d).astype(q.dtype)

    def _distributed_attend(self, q, cache, kv_len, positions, cfg):
        """Distributed CAM search (paper Sec. IV-C at cluster scale).

        The packed-binary cache is sequence-sharded across the mesh; each
        shard runs the BA-CAM scoring + two-stage top-k LOCALLY, shards
        exchange only their k candidates (k*(8 B) per query per shard — vs
        gathering the full N-score matchline vector), the global
        top-k/softmax is computed redundantly everywhere, and
        contextualization is a masked partial sum over local V rows
        finished by one psum.
        """
        env = jax.sharding.get_abstract_mesh()
        axes = tuple(a for a in ("pod", "data", "model")
                     if a in getattr(env, "shape", {}) and env.shape[a] > 1)
        if not axes:
            return self._cache_attend(q, cache, kv_len, positions, cfg)
        from jax.sharding import PartitionSpec as P

        spec = self.spec(cfg)
        b, h, sq, d = q.shape
        hkv = cfg.n_kv_heads
        g = h // hkv
        skv = cache["v"].shape[2]
        n_shards = math.prod(env.shape[a] for a in axes)
        s_local = skv // n_shards
        qb = sign_pm1(q.astype(jnp.float32))
        q_scale = jnp.mean(jnp.abs(q.astype(jnp.float32)), axis=-1)
        qp = bacam.pack_bits(qb).reshape(b, hkv, g * sq, d // 32)

        k_top = spec.k_top

        def local_fn(qp_l, kp_l, v_l, kscale_l, qscale_l, pos_l, kvlen_l):
            # shard offset along the cache sequence
            idx = 0
            for a in axes:
                idx = idx * env.shape[a] + jax.lax.axis_index(a)
            offset = idx * s_local
            scores = bacam.hamming_scores_packed(
                qp_l, kp_l, d).astype(jnp.float32)
            kpos = offset + jnp.arange(
                s_local, dtype=jnp.int32)[None, None, None]
            qpos = jnp.broadcast_to(pos_l[:, None, :], (b, hkv, sq))
            qpos = jnp.broadcast_to(
                qpos[:, :, None, :], (b, hkv, g, sq)).reshape(
                b, hkv, g * sq)[..., None]
            ok = (kpos < kvlen_l.reshape(b, 1, 1, 1)) & (kpos <= qpos)
            if cfg.window is not None:
                ok = ok & (kpos > qpos - cfg.window)
            masked = jnp.where(ok, scores, NEG_INF)
            lv, li = two_stage_topk(
                masked, k=k_top, group_size=spec.group_size,
                stage1_k=spec.stage1_k)  # local top-k
            li = li + offset  # globalize indices
            # exchange candidates only: (B,Hkv,R,k) per shard
            cv = jax.lax.all_gather(lv, axes, axis=-1, tiled=True)
            ci = jax.lax.all_gather(li, axes, axis=-1, tiled=True)
            top_v, sel = jax.lax.top_k(cv, k_top)  # identical on every shard
            top_i = jnp.take_along_axis(ci, sel, axis=-1)
            scale = 1.0 / (d**0.5)
            temp = (qscale_l.reshape(b, hkv, g * sq)[..., None]
                    * kscale_l[:, :, None, None])
            w, valid = topk_softmax_weights(top_v, temp, scale)
            # partial contextualization over local V rows
            mine = (top_i >= offset) & (top_i < offset + s_local) & valid
            loc = jnp.clip(top_i - offset, 0, s_local - 1)
            v_exp = v_l[:, :, None]  # (B,Hkv,1,S_local,D)
            v_sel = jnp.take_along_axis(v_exp, loc[..., None], axis=-2)
            contrib = jnp.einsum(
                "bhrk,bhrkd->bhrd",
                jnp.where(mine, w, 0.0).astype(jnp.float32),
                v_sel.astype(jnp.float32))
            return jax.lax.psum(contrib, axes)

        seq_spec = P(None, None, axes, None)
        out = jax.shard_map(
            local_fn,
            mesh=env,
            in_specs=(P(), seq_spec,
                      P(None, None, axes, None), P(), P(), P(), P()),
            out_specs=P(),
        )(qp, cache["k_packed"], cache["v"], cache["k_scale"], q_scale,
          positions, kv_len)
        out = out.reshape(b, hkv, g, sq, d).reshape(b, h, sq, d)
        return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# hybrid: flash-scored prefill + CAM decode


class HybridBackend(CamformerBackend):
    """Flash-prefill hybrid: dense flash-scored fused prefill chunks +
    CAM paged decode — the analog/digital split of charge-based hybrid
    attention accelerators layered on X-Former-style mixed tiling.

    The paged pools carry BOTH key representations: a dense ``k_pages``
    pool for the Sq > 1 chunk path — chunked prefill, the TTFT-critical
    hot path, runs the fused paged flash kernel with an EXACT softmax —
    and the bit-packed ``kp_pages`` + running ``k_scale`` for the CAM
    decode path (two-stage top-k search per generated token).  Every
    page write updates both, so either attend is always current.

    Speculative VERIFY chunks (``cfg.spec_verify``) deliberately take
    the CAM path with sequential per-query scales (``_chunk_scale_seq``
    + the ``k_means`` stash): speculation's exactness contract
    (serving/speculate.py) is that verify logits reproduce what the
    TARGET's sequential decode would emit — and this backend's decode
    is CAM, so flash-scoring the verify chunk would break token-level
    acceptance.  Only non-verify prefill chunks flash-score.

    Cost: the dense K pool adds ``H_kv * D * itemsize`` bytes/token over
    camformer (values dominate either way); in exchange prefill keeps
    full softmax fidelity AND live-page-proportional reads.
    """

    name = "hybrid"
    mode = "camformer"

    def page_spec(self, cfg, n_pages, page_size, max_batch, dtype):
        spec = super().page_spec(cfg, n_pages, page_size, max_batch, dtype)
        spec["k_pages"] = (jax.ShapeDtypeStruct(
            (n_pages, cfg.n_kv_heads, page_size, cfg.head_dim), dtype),
            (None, "kv_heads", None, "head_dim"))
        return spec

    def cache_bytes_per_token(self, cfg, dtype):
        d = cfg.head_dim
        item = jnp.dtype(dtype).itemsize
        # packed keys + dense keys (flash prefill) + dense values
        return cfg.n_kv_heads * (d // 8 + 2 * d * item)

    def _paged_write(self, cache, k, v, positions, page_table, kv_len, cfg,
                     base=None):
        pages = super()._paged_write(cache, k, v, positions, page_table,
                                     kv_len, cfg, base=base)
        page = cache["k_pages"].shape[2]
        b = k.shape[0]
        phys, row = _page_phys_rows(
            page_table, positions.astype(jnp.int32), page,
            kv_len.reshape(b).astype(jnp.int32))
        pages["k_pages"] = cache["k_pages"].at[phys, :, row].set(
            k.astype(cache["k_pages"].dtype).transpose(0, 2, 1, 3))
        return pages

    def prefill(self, q, k, v, cfg, *, causal=True, positions=None,
                window=None):
        # whole-prompt prefill / training attend: flash-scored (exact
        # softmax), matching the paged chunk path below
        return get_backend("dense").prefill(
            q, k, v, cfg, causal=causal, positions=positions, window=window)

    def paged_decode(self, q, cache, k, v, positions, page_table, kv_len,
                     cfg, *, base=None):
        if q.shape[2] > 1 and not cfg.spec_verify:
            # flash-scored prefill chunk over the dense key pool; the
            # packed pool and running k_scale were updated by the same
            # write, so the CAM decode that follows reads current state
            new_cache = self._paged_write(
                cache, k, v, positions, page_table, kv_len, cfg, base=base)
            out = get_backend("dense")._paged_attend(
                q, new_cache, positions, page_table, kv_len, cfg)
            return out, new_cache
        # decode rows and speculative verify chunks: the CAM search
        # path (verify must reproduce the sequential CAM decode)
        return super().paged_decode(q, cache, k, v, positions, page_table,
                                    kv_len, cfg, base=base)

    def paged_io_stats(self, cfg, dtype, *, kv_len, page_size,
                       n_table_pages):
        stats = super().paged_io_stats(
            cfg, dtype, kv_len=kv_len, page_size=page_size,
            n_table_pages=n_table_pages)
        # decode columns stay CAM; prefill chunks read the DENSE pools
        item = jnp.dtype(dtype).itemsize
        row = 2 * cfg.n_kv_heads * cfg.head_dim * item
        live_rows = -(-max(kv_len, 1) // page_size) * page_size
        stats["prefill_fused_read_bytes"] = live_rows * row
        stats["prefill_gather_read_bytes"] = n_table_pages * page_size * row
        return stats


register_backend(DenseBackend())
register_backend(BinaryBackend())
register_backend(CamformerBackend())
register_backend(HybridBackend())
