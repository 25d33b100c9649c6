"""Bit-sliced binary-integer VMM on the BA-CAM engine (paper Sec. II-B1).

"For higher-precision [operands], we decompose entries into binary slices
(LSB -> MSB) and run per-slice BIMM.  Slice outputs are digitally shifted
and accumulated, adding precision without changing the CAM path.  This
supports binary-integer MatMul and quantized int2/int4/int8."

We reuse the packed-popcount kernel per slice: a {0,1} bit-plane p maps to
±1 as p± = 2p − 1, and for x ∈ {−1,+1}^d

    x · p = (x · p± + x · 1) / 2            (x·1 = row sum of x)

so each slice costs exactly one BA-CAM search plus a shared row-sum.  The
two's-complement MSB slice enters with weight −2^(bits−1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bacam import pack_bits
from repro.kernels.bacam_mvm import bacam_mvm


@functools.partial(jax.jit, static_argnames=("bits", "block_q", "block_k", "interpret"))
def bitslice_vmm(
    x_pm1: jax.Array,
    w_int: jax.Array,
    *,
    bits: int,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    """y = x_pm1 @ w_int^T via per-slice BA-CAM searches.

    x_pm1: (B, R, d) in {−1,+1}; w_int: (B, N, d) ints in
    [−2^(bits−1), 2^(bits−1)).  Returns (B, R, N) int32 — exact.

    R/N must be multiples of the block sizes (ops.py pads).
    """
    b, r, d = x_pm1.shape
    n = w_int.shape[1]
    xp = pack_bits(x_pm1)
    row_sum = x_pm1.astype(jnp.int32).sum(axis=-1)[:, :, None]  # x·1, shared

    u = w_int.astype(jnp.int32).astype(jnp.uint32)
    out = jnp.zeros((b, r, n), jnp.int32)
    for s in range(bits):  # static: one BA-CAM pass per slice
        plane = ((u >> s) & jnp.uint32(1)).astype(jnp.int32)
        pp = pack_bits(plane)  # pack_bits keys on (value > 0)
        dot_pm = bacam_mvm(
            xp, pp, d=d, block_q=block_q, block_k=block_k, interpret=interpret
        )  # x · p±
        dot01 = (dot_pm + row_sum) // 2  # x · p  (exact: same parity)
        weight = -(1 << s) if s == bits - 1 else (1 << s)
        out = out + weight * dot01
    return out
