"""Self-speculative decoding: binary-draft / target-verify multi-token steps.

CAMformer's thesis — binarized associative scoring is a near-lossless,
radically cheaper stand-in for dense attention — makes the ``binary``
backend a FREE draft model for the very stack it approximates: the same
weights, every layer forced to ``cfg.spec_backend``, drafting from its
own cheap paged cache.  Each tick the drafter proposes up to ``spec_k``
tokens per DECODING slot and the target stack (dense / camformer /
mixed, unchanged) verifies all ``k+1`` positions in ONE fused device
step over the existing Sq>1 chunked-prefill seam (``offsets`` /
``scale_base``), so a tick that accepts ``a`` drafts emits ``a+1``
tokens for one target forward.

Exactness (keyed-sample-match acceptance)
-----------------------------------------

The emitted tokens are the TARGET's keyed samples, never the drafter's:
position ``L+j`` of the verify pass samples ``s_j`` with
``sample_step_keyed`` at generated-token index ``i+j`` — a pure function
of ``(seed, rid, index)``, exactly the draw sequential decode would
make at that index from the same cache state.  Draft ``d_j`` is
accepted iff ``d_j == s_{j-1}`` (it matches what would have been
emitted anyway), and acceptance stops at the first mismatch, so the
accepted prefix ``s_0 .. s_acc`` is token-for-token the sequential
output for ANY temperature — greedy reduces to standard greedy
speculative decoding, and ``spec_k=0`` never enters this module.  The
drafter maximizes its hit rate by sampling with the SAME keyed draws at
the SAME indices (shared-randomness coupling), so where the binary
approximation agrees with the target, the draft is accepted by
construction.

Cache discipline
----------------

Target and drafter share ONE page table / allocator: the drafter's pool
(``page_specs`` of the draft config) uses the same physical page ids,
so admission, COW prefix forks, preemption, and rollback are planned
once.  The drafter runs ``m = spec_k+1`` single-token steps so its pool
stays in positional lockstep with the target's verify writes (the last
step's sample is discarded; per-row steps beyond ``n_tok`` run with
``kv_len == 0`` — the backend inert-row contract — so they touch
neither pages nor running statistics).  Rejected suffixes roll back on
the HOST via ``PagedKVCache.truncate_to`` (scheduler ``resolve_spec``);
device-side, the rejected positions hold garbage beyond ``kv_len`` —
invisible to masked attention and overwritten by the next tick.

``k_scale`` (binary/camformer softmax-temperature bookkeeping) keeps
sequential-decode semantics throughout: the verify pass runs under
``spec_verify`` — each chunk column attends with the running scale AT
ITS OWN POSITION and the chunk's per-position key means are stashed in
the ``k_means`` pool leaf — so ``repair_k_scale`` reconstructs the
running mean at the accepted length exactly, and ``select_k_scale``
rolls the drafter back by picking its per-step snapshot.  Without this
the chunk-granular scale (a mean contaminated by the chunk's rejected
future keys) perturbs verify logits at the percent level and breaks
greedy token-for-token identity with the sequential loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.serving import sampler as S

__all__ = ["draft_config", "sample_positions_keyed", "accept_prefix",
           "repair_k_scale", "select_k_scale", "build_spec_prefill",
           "build_spec_step"]


def draft_config(cfg):
    """The drafter's model config: the SAME architecture with every
    layer's attention forced to ``cfg.spec_backend`` (weights are
    shared; only the attention realization and its page layout change)."""
    return cfg.replace(layer_backends=None, attn_backend=cfg.spec_backend)


def sample_positions_keyed(logits, keys, index, temps, top_ks, top_ps):
    """``sample_step_keyed`` over every position of a verify batch.

    logits: (B, M, V); index: (B, M) generated-token index per position;
    keys/temps/top_ks/top_ps: per-slot (B, ...) rows shared across
    positions.  Returns (B, M) int32 — column ``j`` is the draw the
    sequential loop would make at ``index[:, j]``.
    """
    def one(lg, ix):
        return S.sample_step_keyed(lg, keys, ix, temps, top_ks, top_ps)

    return jax.vmap(one, in_axes=(1, 1), out_axes=1)(
        logits, index.astype(jnp.int32))


def accept_prefix(drafts, samples, n_tok):
    """Length of the accepted prefix per row, INCLUDING the bonus token.

    drafts: (B, M) the verify inputs (column 0 is the previous tick's
    token, columns 1.. the draft proposals); samples: (B, M) the
    target's keyed samples; n_tok: (B,) valid positions per row.
    Draft ``drafts[:, j]`` is accepted iff it equals ``samples[:, j-1]``
    and every earlier draft was accepted; the return value
    ``n_valid = accepted + 1`` counts the emitted tokens
    ``samples[:, :n_valid]`` (0 for rows with ``n_tok == 0``).
    """
    b, m = drafts.shape
    if m > 1:
        j = jnp.arange(1, m, dtype=jnp.int32)[None]
        ok = (drafts[:, 1:] == samples[:, :-1]) & (j < n_tok[:, None])
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    else:
        acc = jnp.zeros((b,), jnp.int32)
    return jnp.where(n_tok > 0, acc + 1, 0).astype(jnp.int32)


def repair_k_scale(new, old, pos, base, n_tok, n_valid):
    """Roll every ``k_scale`` leaf back from ``n_tok`` written positions
    to the ``n_valid`` accepted ones — EXACTLY.

    The verify pass merged this tick's chunk into the running mean and
    stashed the chunk's per-position key means in the ``k_means`` leaf
    (backends under ``spec_verify``; see ``_chunk_scale_seq``).  The
    repaired scale is therefore reconstructible at any accepted length:
    ``s' = (s0*n0 + sum(means[:kept])) / (n0 + kept)`` with ``n0 =
    pos - base`` prior counted positions and ``kept = n_valid`` — the
    value a sequential decode loop would have stored after its last
    accepted step.  Rows with nothing rejected (or inert rows,
    ``n_tok == 0``) keep the post-verify value bit-exactly.

    ``new``/``old`` are the post-/pre-step cache trees (uniform layer-
    stacked dict or per-layer tuple); ``k_scale`` leaves have shape
    (..., B, H), ``k_means`` (..., B, H, m), and the per-slot stats
    broadcast over leading layer axes.
    """
    n0 = (pos - base).astype(jnp.float32)[:, None]
    w = n_tok.astype(jnp.float32)[:, None]
    kept = n_valid.astype(jnp.float32)[:, None]
    exact = (kept >= w) | (w <= 0)

    def one(nl, ol):
        if "k_scale" not in nl or "k_means" not in nl:
            return nl
        s1, s0 = nl["k_scale"], ol["k_scale"]
        cum = jnp.cumsum(nl["k_means"], axis=-1)
        m = cum.shape[-1]
        ix = jnp.clip(n_valid - 1, 0, m - 1).astype(jnp.int32)
        ix = jnp.broadcast_to(ix.reshape((-1, 1, 1)), cum.shape[:-1] + (1,))
        kept_sum = jnp.take_along_axis(cum, ix, axis=-1)[..., 0]
        fixed = (s0 * n0 + kept_sum) / jnp.maximum(n0 + kept, 1.0)
        return {**nl, "k_scale": jnp.where(exact, s1, fixed)}

    if isinstance(new, tuple):
        return tuple(one(nl, ol) for nl, ol in zip(new, old))
    return one(new, old)


def _kscales(tree):
    """The ``k_scale`` leaves of a cache tree (layer-structural snapshot;
    ``None`` per layer when the backend keeps no running scale)."""
    if isinstance(tree, tuple):
        return tuple(layer.get("k_scale") for layer in tree)
    return tree.get("k_scale")


def select_k_scale(final, snaps, n_valid):
    """Drafter-side rollback: pick each row's ``k_scale`` from the step
    snapshot of its LAST accepted draft step.

    The draft loop runs sequentially, so the exact rolled-back scale is
    simply the value after step ``n_valid - 1`` — no reconstruction.
    ``snaps`` is the per-step list of ``_kscales`` snapshots (length m);
    rows with ``n_valid == 0`` were inert all tick, so snapshot 0 holds
    their untouched pre-tick value.
    """
    idx = jnp.clip(n_valid - 1, 0, len(snaps) - 1).astype(jnp.int32)

    def one(layer, *vals):
        if "k_scale" not in layer:
            return layer
        stk = jnp.stack(vals, axis=-1)  # (..., B, H, m)
        ix = jnp.broadcast_to(idx.reshape((-1, 1, 1)),
                              stk.shape[:-1] + (1,))
        return {**layer,
                "k_scale": jnp.take_along_axis(stk, ix, axis=-1)[..., 0]}

    if isinstance(final, tuple):
        return tuple(one(layer, *(s[i] for s in snaps))
                     for i, layer in enumerate(final))
    return one(final, *snaps)


def build_spec_prefill(md, cfg, dcfg, hot: bool):
    """The fused prefill step with speculation on: the target prefill
    (unchanged — its last-token sample is the slot's first token) plus a
    drafter-stack prefill over the same chunk batch, so the draft pool
    holds the prompt KV before the slot's first speculative tick."""

    def spec_prefill_step(params, tokens, lens, offsets, scale_base, caches,
                          dcaches, pt, keys, index, temps, top_ks, top_ps):
        batch = {"tokens": tokens, "lens": lens, "offsets": offsets,
                 "scale_base": scale_base}
        logits, caches = md.prefill_paged(params, batch, caches, pt, cfg)
        _, dcaches = md.prefill_paged(params, batch, dcaches, pt, dcfg)
        if hot:
            first = S.sample_step_keyed(logits, keys, index, temps,
                                        top_ks, top_ps)
        else:
            first = S.greedy(logits)
        return first, caches, dcaches

    return spec_prefill_step


def build_spec_step(md, cfg, dcfg, m: int, hot: bool):
    """The fused speculative decode step (ONE jit per tick).

    Per live row with ``n_tok`` dispatched indices starting at position
    ``pos`` and generated-token index ``index``:

      1. DRAFT: ``m`` sequential drafter steps (binary stack, own pool,
         same page table), sampling proposals with the target's keyed
         draws at the same indices; step ``j`` past ``n_tok`` is inert.
      2. VERIFY: the target scores all ``m`` positions in one Sq>1 pass
         (``verify_paged`` over the chunked-prefill seam) and draws its
         keyed samples ``s_0..s_{m-1}``.
      3. ACCEPT: longest prefix of drafts matching the samples;
         ``n_valid = accepted + 1`` tokens are emitted.
      4. REPAIR: ``k_scale`` leaves of BOTH pools rescale to the
         accepted count; the token buffer takes the last VALID sample
         (rows outside this tick keep their buffered token).

    Returns ``(packed (B, m+1) int32 — samples ++ n_valid — the tick's
    single readback, tok_buf (B,), caches, dcaches)``.
    """

    def spec_step(params, tok_prev, fresh, fresh_mask, live_mask, pos, n_tok,
                  caches, dcaches, pt, base, keys, index, temps, top_ks,
                  top_ps):
        pos = pos.astype(jnp.int32)
        n_tok = n_tok.astype(jnp.int32)
        index = index.astype(jnp.int32)
        t0 = jnp.where(live_mask,
                       jnp.where(fresh_mask, fresh, tok_prev), 0)
        caches0 = caches

        # -- 1. draft: m lockstep single-token steps ------------------
        toks = [t0]
        tok = t0
        snaps = []  # per-step k_scale snapshots for exact rollback
        for j in range(m):
            kvl = jnp.where(live_mask & (j < n_tok), pos + j + 1, 0)
            dlogits, dcaches = md.decode_paged(
                params, tok, pos + j, kvl, dcaches, pt, dcfg, base=base)
            snaps.append(_kscales(dcaches))
            if j < m - 1:  # the last step only writes lockstep KV
                if hot:
                    tok = S.sample_step_keyed(dlogits, keys, index + j,
                                              temps, top_ks, top_ps)
                else:
                    tok = S.greedy(dlogits)
                toks.append(tok)
        drafts = jnp.stack(toks, axis=1)  # (B, m)

        # -- 2. verify: one fused Sq>1 target pass --------------------
        lens = jnp.where(live_mask, pos + n_tok, 0)
        batch = {"tokens": drafts, "lens": lens, "offsets": pos,
                 "scale_base": base}
        logits, caches = md.verify_paged(params, batch, caches, pt, cfg)
        idx = index[:, None] + jnp.arange(m, dtype=jnp.int32)[None]
        if hot:
            samples = sample_positions_keyed(logits, keys, idx, temps,
                                             top_ks, top_ps)
        else:
            samples = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # -- 3. accept-prefix -----------------------------------------
        n_valid = accept_prefix(drafts, samples, n_tok)

        # -- 4. repair + token buffer ---------------------------------
        caches = repair_k_scale(caches, caches0, pos, base, n_tok, n_valid)
        dcaches = select_k_scale(dcaches, snaps, n_valid)
        last = jnp.take_along_axis(
            samples, jnp.clip(n_valid - 1, 0, m - 1)[:, None], axis=1)[:, 0]
        tok_buf = jnp.where(live_mask, last, tok_prev)
        packed = jnp.concatenate([samples, n_valid[:, None]], axis=1)
        return packed, tok_buf, caches, dcaches

    return spec_step
