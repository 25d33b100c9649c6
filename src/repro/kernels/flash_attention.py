"""Pallas TPU kernel: dense flash attention (forward), the paper's baseline.

Online-softmax attention with O(S) memory, used (a) as the full-precision
dense baseline CAMformer is compared against, and (b) for serving prefill.
Layout is per-head 3D (B*, S, D); the ops wrapper folds (batch, heads).

Grid (B, Sq/bq, Skv/bk) with the KV dimension innermost and sequential
("arbitrary" on TPU); running max/denominator/accumulator live in VMEM
scratch that persists across the KV sweep (canonical TPU flash pattern).

VMEM (bq=bk=512, D<=256): q/k/v blocks 3*512*256*4 B = 1.5 MiB + acc
512*256*4 = 0.5 MiB + s/p 512*512*4 = 1 MiB  =>  ~3 MiB of 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.topk import NEG_INF


def _kernel(
    off_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None, block_q: int, block_k: int,
):
    i, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    qpos = off_ref[0, 0] + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = jnp.ones_like(kpos, dtype=jnp.bool_)
    if causal:
        ok = jnp.logical_and(ok, kpos <= qpos)
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(ok, p, 0.0)  # fully-masked rows stay all-zero
    l_scr[:, 0] = l_scr[:, 0] * alpha + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:, 0] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array | int = 0,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    """Flash attention forward. q: (B, Sq, D); k, v: (B, Skv, D)."""
    b, sq, d = q.shape
    skv = k.shape[1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv)
    off = jnp.full((1, 1), q_offset, jnp.int32)
    grid = (b, sq // block_q, skv // block_k)
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b_, i, j: (0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, i, j: (b_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(off, q, k, v)
