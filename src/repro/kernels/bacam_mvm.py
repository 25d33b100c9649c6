"""Pallas TPU kernel: BA-CAM binary matrix-vector/matrix multiply.

Computes signed binary attention scores  s = d - 2*popcount(q ^ k)  from
bit-packed operands.  This is the TPU-native dual of the paper's BA-CAM
array (DESIGN.md §2): the charge-sharing matchline becomes XNOR +
``lax.population_count`` over uint32 lanes; CAM array tiling (Fig. 4 steps
①-④) becomes the BlockSpec grid, with the horizontal-tile concatenation
realized by the (i, j) output grid and the vertical-tile accumulation
register realized by the in-register accumulation over packed words.

Memory layout is the point: keys are stored 1 bit/element (uint32-packed),
so a (Skv, d) key matrix streams HBM->VMEM at 1/16 the bytes of bf16 —
the kernel is *compute*-dominated on the VPU rather than bandwidth-
dominated, mirroring how the analog array removes the memory bottleneck.

VMEM budget (TPU v5e, 128-aligned): default blocks bq=256, bk=512, W<=8:
  q: 256*8*4 B = 8 KiB, k: 512*8*4 B = 16 KiB, acc: 256*512*4 B = 512 KiB
  + out block 512 KiB  =>  ~1 MiB of 16 MiB VMEM  (room for double-buffer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, o_ref, *, d: int, words: int):
    """One (bq, bk) output tile: accumulate popcounts over packed words."""
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    acc = jnp.zeros((bq, bk), jnp.int32)
    for w in range(words):  # static unroll: words = d/32 in {2,4,8}
        x = jnp.bitwise_xor(q_ref[0, :, w][:, None], k_ref[0, :, w][None, :])
        acc = acc + jax.lax.population_count(x).astype(jnp.int32)
    o_ref[0] = jnp.int32(d) - 2 * acc


@functools.partial(
    jax.jit, static_argnames=("d", "block_q", "block_k", "interpret")
)
def bacam_mvm(
    q_packed: jax.Array,
    k_packed: jax.Array,
    *,
    d: int,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    """Binary scores (B, R, Skv) int32 from packed (B, R, W)/(B, Skv, W).

    R and Skv must be multiples of the block sizes (ops.py pads).
    """
    b, r, words = q_packed.shape
    skv = k_packed.shape[1]
    assert words * 32 == d, (words, d)
    assert r % block_q == 0 and skv % block_k == 0, (r, skv, block_q, block_k)
    grid = (b, r // block_q, skv // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, d=d, words=words),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, words), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_k, words), lambda b_, i, j: (b_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, block_k), lambda b_, i, j: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, r, skv), jnp.int32),
        interpret=interpret,
    )(q_packed, k_packed)
