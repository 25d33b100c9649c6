"""Gradient compression for cross-pod reduction (int8 + error feedback).

At multi-pod scale the inter-pod links (DCI) are the scarcest bandwidth, so
the cross-pod gradient reduction is compressed: int8 quantization with a
per-tensor scale and an error-feedback accumulator (1-bit-Adam style) that
re-injects quantization residuals the next step — keeping convergence
unbiased in the long run while cutting pod-boundary bytes 4x vs fp32.

Usage (inside a jitted step, via shard_map over the `pod` axis):
    grads, err = compressed_pod_mean(grads, err, axis="pod")
A standalone reference (`compressed_mean_ref`) backs the property tests.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

__all__ = ["quantize_int8", "dequantize_int8", "compressed_psum_leaf",
           "compressed_pod_mean", "compressed_mean_ref"]


def quantize_int8(x: jax.Array):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array):
    return q.astype(jnp.float32) * scale


def compressed_psum_leaf(g: jax.Array, err: jax.Array, axis: str):
    """Error-feedback int8 reduction of one gradient leaf over `axis`.

    int8 payloads are all-gathered together with their per-pod scales and
    dequantized EXACTLY per pod before summation, so the local feedback
    residual x - q*scale telescopes: the time-averaged delivered gradient
    equals the true mean to within max_scale/(2T) (provably unbiased; the
    property test asserts it).  For the pod axis (n small) the int8
    all-gather also moves fewer bytes than an fp32 ring all-reduce:
    (n-1)/n * n * 1 B  vs  2 * 4 B per element.

    Returns (mean gradient f32, new error accumulator)."""
    n = jax.lax.axis_size(axis)
    x = g.astype(jnp.float32) + err
    q, scale = quantize_int8(x)
    new_err = x - dequantize_int8(q, scale)  # exact local residual
    qs = jax.lax.all_gather(q, axis)  # (n, ...)
    scales = jax.lax.all_gather(scale, axis)  # (n,)
    shape = (-1,) + (1,) * q.ndim
    total = jnp.sum(qs.astype(jnp.float32) * scales.reshape(shape), axis=0)
    return total / n, new_err


def compressed_pod_mean(grads, err_tree, axis: str = "pod"):
    """Tree version of compressed_psum_leaf (call inside shard_map)."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(err_tree)
    out_g, out_e = [], []
    for g, e in zip(flat_g, flat_e):
        mg, ne = compressed_psum_leaf(g, e, axis)
        out_g.append(mg)
        out_e.append(ne)
    return (jax.tree_util.tree_unflatten(treedef, out_g),
            jax.tree_util.tree_unflatten(treedef, out_e))


def compressed_mean_ref(xs, errs):
    """Pure-numpy-style oracle: per-replica quantize w/ feedback, mean.

    xs: (n, ...) stacked replica gradients; errs: same.  Returns
    (mean estimate, new errs) matching compressed_psum_leaf semantics with
    equal scales folded to the mean scale.
    """
    n = xs.shape[0]
    x = xs.astype(jnp.float32) + errs
    scales = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim))) / 127.0 + 1e-12
    sc = scales.reshape((-1,) + (1,) * (x.ndim - 1))
    q = jnp.clip(jnp.round(x / sc), -127, 127)
    new_errs = x - q * sc  # exact local residual (per-pod scales)
    return (q * sc).sum(0) / n, new_errs
