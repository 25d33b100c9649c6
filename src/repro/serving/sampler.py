"""Token samplers for the serving engine.

``sample_step_keyed`` is the engine's per-tick entry point, fused INSIDE
the jitted device step: every row of the decode batch carries its own
temperature / top-k / top-p (the per-request ``SamplingParams``) plus its
own rng key and generated-token index, so the sampled ids are a pure
function of ``(request key, token index)`` — completely independent of
which engine tick, slot, or batch composition produced them.  That is
what makes the overlapped (dispatch-ahead) engine loop token-for-token
identical to the synchronous one, and preemption/resume regenerate the
same continuation.  ``sample_step`` is the single-key variant kept for
direct callers; ``apply_top_k`` / ``apply_top_p`` are the row-wise logit
filters, exposed separately so tests can pin them against a reference
implementation.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["greedy", "sample", "sample_step", "sample_step_keyed",
           "request_key", "apply_top_k", "apply_top_p"]

_MASKED = -1e9  # filtered logits (matches the vocab-padding mask value)


@jax.named_scope("sample")
def greedy(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def apply_top_k(logits: jax.Array, k) -> jax.Array:
    """Keep each row's k highest logits, mask the rest to -1e9.

    logits: (..., V); k: scalar or (...,) int32 — 0 disables the filter
    for that row.
    """
    v = logits.shape[-1]
    k = jnp.broadcast_to(jnp.asarray(k, jnp.int32), logits.shape[:-1])
    eff = jnp.where(k > 0, jnp.minimum(k, v), v)
    srt = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
    kth = jnp.take_along_axis(srt, (eff - 1)[..., None], axis=-1)
    return jnp.where(logits < kth, _MASKED, logits)


def apply_top_p(logits: jax.Array, p) -> jax.Array:
    """Nucleus filter: keep each row's smallest high-probability set whose
    cumulative softmax mass reaches p, mask the rest to -1e9.

    logits: (..., V); p: scalar or (...,) float — the top-1 token is
    always kept; p >= 1.0 keeps every token with nonzero probability.
    Ties break by stable descending sort, matching a numpy
    ``argsort(-x, kind="stable")`` reference.
    """
    p = jnp.broadcast_to(jnp.asarray(p, jnp.float32),
                         logits.shape[:-1])[..., None]
    idx = jnp.argsort(-logits, axis=-1)  # stable descending
    sl = jnp.take_along_axis(logits, idx, axis=-1)
    sp = jax.nn.softmax(sl, axis=-1)
    cum = jnp.cumsum(sp, axis=-1)
    keep = (cum - sp) < p  # mass BEFORE this token is still short of p
    masked = jnp.where(keep, sl, _MASKED)
    inv = jnp.argsort(idx, axis=-1)  # inverse permutation
    return jnp.take_along_axis(masked, inv, axis=-1)


def sample(logits: jax.Array, rng: jax.Array, *, temperature: float = 1.0,
           top_k: int = 0, top_p: float = 1.0) -> jax.Array:
    """Single-policy sampling (python scalars). logits: (B, V)."""
    if temperature <= 0.0:
        return greedy(logits)
    z = logits / temperature
    if top_k:
        z = apply_top_k(z, top_k)
    if top_p < 1.0:
        z = apply_top_p(z, top_p)
    return jax.random.categorical(rng, z, axis=-1).astype(jnp.int32)


def sample_step(logits: jax.Array, rng: jax.Array, temperature, top_k,
                top_p) -> jax.Array:
    """Per-row sampling for one engine tick.

    logits: (B, V); temperature/top_k/top_p: (B,) arrays from each slot's
    ``SamplingParams``.  Rows with temperature <= 0 are greedy (their
    top-k/top-p values are ignored); the rest filter then draw
    categorically at their own temperature.
    """
    g = greedy(logits)
    t = jnp.asarray(temperature, jnp.float32)
    safe_t = jnp.where(t > 0, t, 1.0)[:, None]
    z = apply_top_p(apply_top_k(logits / safe_t, top_k), top_p)
    c = jax.random.categorical(rng, z, axis=-1).astype(jnp.int32)
    return jnp.where(t > 0, c, g)


def request_key(seed: int, rid: int) -> np.ndarray:
    """Deterministic per-request raw key data, derived on the HOST (no
    device work at admission time).  The engine folds the generated-token
    index in on-device, so sampling is a pure function of (seed, rid,
    index) — identical under sync/overlapped loops, slot reassignment,
    and preemption/resume."""
    return np.random.SeedSequence(entropy=(int(seed), int(rid))).generate_state(
        2, dtype=np.uint32)


@jax.named_scope("sample")
def sample_step_keyed(logits, keys, index, temperature, top_k, top_p):
    """Per-row keyed sampling for one engine tick (fused into the step).

    logits: (B, V); keys: (B, 2) uint32 raw per-request key data;
    index: (B,) int32 generated-token index being sampled;
    temperature/top_k/top_p: (B,).  Rows with temperature <= 0 are greedy
    and never consume randomness; the rest filter then draw categorically
    from ``fold_in(key, index)`` — their draws do not depend on tick
    scheduling or on which other rows share the batch.
    """
    g = greedy(logits)
    t = jnp.asarray(temperature, jnp.float32)
    safe_t = jnp.where(t > 0, t, 1.0)[:, None]
    z = apply_top_p(apply_top_k(logits / safe_t, top_k), top_p)

    def draw(key, i, row):
        return jax.random.categorical(jax.random.fold_in(key, i), row)

    c = jax.vmap(draw)(keys, index.astype(jnp.int32), z).astype(jnp.int32)
    return jnp.where(t > 0, c, g)
