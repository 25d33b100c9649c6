"""Fused paged flash-PREFILL (Sq > 1 chunks) pinned against the
page-gather oracle, kernel-level and through ServeEngine, plus the
flash-prefill hybrid backend and the analytic paged_io_stats pins.

Tolerance policy mirrors test_paged_flash.py: ``prefill_impl="fused"``
vs ``"gather"`` share the page-write path and differ only in the Sq > 1
chunk attend, whose dense/binary arithmetic is a softmax over identical
logits — engine comparisons are TOKEN-FOR-TOKEN exact, kernel
comparisons float-noise allclose.  The hybrid backend's verify chunks
deliberately stay on the CAM path (speculation's exactness contract),
so its fused-vs-gather engine legs cover both chunk kinds.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.configs import smoke_config  # noqa: E402
from repro.core.attention import (AttentionSpec, attention,  # noqa: E402
                                  binary_paged_attention)
from repro.core.backend import get_backend, list_backends  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.models import get_model_def  # noqa: E402
from repro.models.module import init_params  # noqa: E402
from repro.serving import (Request, SamplingParams,  # noqa: E402
                           ServeEngine)

_SLOW = pytest.mark.slow


def _cfg(backend=None, **kw):
    return smoke_config("codeqwen1.5-7b").replace(attn_backend=backend, **kw)


def _pools(key, b=2, hkv=2, d=32, page=8, np_=5, n_pages=12, sq=4):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    k_pages = jax.random.normal(k1, (n_pages, hkv, page, d), jnp.float32)
    v_pages = jax.random.normal(k2, (n_pages, hkv, page, d), jnp.float32)
    pt = jax.random.randint(k3, (b, np_), 1, n_pages).astype(jnp.int32)
    q = jax.random.normal(k4, (b, hkv * 2, sq, d), jnp.float32)
    return q, k_pages, v_pages, pt


def _gather_prefill(q, k_pages, v_pages, pt, kv_len, q_pos, *, window=None,
                    binary=False):
    """Sq>1 oracle: logical-order gather + standard causal attend with
    per-row anchors q_pos + s (row s of the chunk)."""
    sq, d = q.shape[2], q.shape[3]
    if binary:
        # the fused kernel binarizes q/k in-register but keeps the
        # 1/sqrt(d) score scale — fold it into q, attend at scale 1
        q = jnp.where(q > 0, 1.0, -1.0) * (1.0 / (d ** 0.5))
        k_pages = jnp.where(k_pages > 0, 1.0, -1.0)
    ck = kref.paged_gather_ref(k_pages, pt)
    cv = kref.paged_gather_ref(v_pages, pt)
    kv_pos = jnp.arange(ck.shape[2], dtype=jnp.int32)[None]
    q_positions = q_pos.reshape(-1, 1) + jnp.arange(sq, dtype=jnp.int32)
    return attention(
        q, ck, cv, AttentionSpec(mode="dense"), causal=True,
        q_positions=q_positions, kv_positions=kv_pos,
        kv_valid=kv_pos < kv_len.reshape(-1, 1), window=window,
        scale=1.0 if binary else None)


# ---------------------------------------------------------------------------
# kernel level: fused Sq>1 (jnp walk AND Pallas interpreter) == oracle


@pytest.mark.parametrize("binary", [False, True])
@pytest.mark.parametrize("window", [None, 6])
def test_prefill_kernel_matches_gather_oracle(window, binary):
    """Chunk start mid-page (slot 0) and exactly on a page boundary
    (slot 1), intra-chunk causality (row s sees positions <= q_pos+s),
    dead table entries past the extent."""
    sq, page = 4, 8
    q, k_pages, v_pages, pt = _pools(jax.random.PRNGKey(0), page=page, sq=sq)
    # kv_len INCLUDES the chunk; q_pos is the chunk's FIRST position
    kv_len = jnp.array([21, 2 * page + sq], jnp.int32)
    q_pos = kv_len - sq
    want = _gather_prefill(q, k_pages, v_pages, pt, kv_len, q_pos,
                           window=window, binary=binary)
    got = kops.paged_flash_prefill(q, k_pages, v_pages, pt, kv_len, q_pos,
                                   window=window, binary=binary)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_prefill_interpret_matches_walk_and_inert_rows_zero():
    """interpret=True (the Pallas-interpreter CPU hatch) and the off-TPU
    jnp walk share the page sweep and accumulation order; a kv_len == 0
    slot keeps the defined all-zeros inert contract at Sq > 1."""
    q, k_pages, v_pages, pt = _pools(jax.random.PRNGKey(1), sq=4)
    kv_len = jnp.array([13, 0], jnp.int32)
    q_pos = jnp.maximum(kv_len - 4, 0)
    walk = kops.paged_flash_prefill(q, k_pages, v_pages, pt, kv_len, q_pos)
    kern = kops.paged_flash_prefill(q, k_pages, v_pages, pt, kv_len, q_pos,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(walk), atol=1e-6)
    assert jnp.all(kern[1] == 0.0)
    assert jnp.all(walk[1] == 0.0)


def test_prefill_sq1_equals_decode_bitwise():
    """The Sq == 1 chunk degenerates to the decode kernel's exact code
    path — bit-identical, not merely close."""
    q, k_pages, v_pages, pt = _pools(jax.random.PRNGKey(2), sq=1)
    kv_len = jnp.array([16, 7], jnp.int32)
    q_pos = kv_len - 1
    pre = kops.paged_flash_prefill(q, k_pages, v_pages, pt, kv_len, q_pos)
    dec = kops.paged_flash_decode(q, k_pages, v_pages, pt, kv_len, q_pos)
    assert jnp.array_equal(pre, dec)


def test_binary_paged_attention_sq_gt1_impls_agree():
    """binary_paged_attention's Sq>1 fused branch (paged_flash_prefill,
    in-register K binarization + folded per-slot temperature) == its
    gather impl."""
    sq = 3
    q, k_pages, v_pages, pt = _pools(jax.random.PRNGKey(3), sq=sq)
    b, hkv = pt.shape[0], k_pages.shape[1]
    kv_len = jnp.array([19, sq], jnp.int32)
    q_pos = (kv_len - sq).reshape(b, 1) + jnp.arange(sq)[None]
    k_scale = jax.random.uniform(jax.random.PRNGKey(4), (b, hkv)) + 0.5
    outs = {
        impl: binary_paged_attention(
            q, k_pages, v_pages, k_scale, pt, kv_len, q_pos, impl=impl)
        for impl in ("fused", "gather")
    }
    np.testing.assert_allclose(np.asarray(outs["fused"]),
                               np.asarray(outs["gather"]), atol=1e-5)


# ---------------------------------------------------------------------------
# engine level: prefill_impl fused == gather token-for-token


def _run_engine(cfg, prefill_impl, prompts, *, max_new=5, spec_k=None,
                mode="sync", **kw):
    md = get_model_def(cfg)
    params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    eng = ServeEngine(md, cfg, params, mode=mode, prefill_slice=8,
                      prefill_impl=prefill_impl, spec_k=spec_k, **kw)
    sampling = SamplingParams(temperature=0.8, top_k=12, max_new=max_new)
    for i, p in enumerate(prompts):
        eng.submit(Request(prompt=list(p), sampling=sampling, rid=i))
    done = {r.rid: r.tokens for r in eng.run()}
    assert eng.kv.free_pages == eng.kv.n_pages - 1  # drained clean
    return done


_SHARED = list(range(30, 42))  # 12 tokens: COW fork mid-page (page 8)
_PROMPTS = [_SHARED + [i, i + 2] for i in (3, 7)] + [[9, 1, 4], [2, 2]]


@pytest.mark.parametrize("backend", ["dense", "binary", "hybrid"])
def test_engine_chunked_prefill_fused_matches_gather(backend):
    """Chunked prefill (prefill_slice=8) with a COW boundary-page fork
    and keyed sampling: the Sq>1 fused flash chunks must reproduce the
    gather oracle token-for-token through the full engine."""
    cfg = _cfg(backend)
    got = {impl: _run_engine(cfg, impl, _PROMPTS)
           for impl in ("fused", "gather")}
    assert got["fused"] == got["gather"]
    assert set(got["fused"]) == set(range(len(_PROMPTS)))


@pytest.mark.parametrize("backend", [
    "binary", pytest.param("hybrid", marks=_SLOW)])
def test_engine_spec_verify_fused_matches_gather(backend):
    """Speculative verify chunks (Sq = k+1) under each prefill_impl:
    exact k_scale sequencing / k_means repair must keep the accepted
    token streams identical.  hybrid's verify chunks take the CAM path
    regardless of impl (exactness contract), so this also pins that
    routing."""
    cfg = _cfg(backend)
    got = {impl: _run_engine(cfg, impl, _PROMPTS, spec_k=3)
           for impl in ("fused", "gather")}
    assert got["fused"] == got["gather"]


@_SLOW
def test_engine_overlap_mixed_stack_fused_matches_gather():
    """A mixed ("dense", "camformer") stack in the overlapped loop:
    dense layers flip chunk realizations, camformer layers stay on
    gather chunks under either impl (no fused Sq>1 CAM kernel)."""
    cfg = smoke_config("codeqwen1.5-7b").replace(
        attn_backend=None, layer_backends=("dense", "camformer"))
    got = {impl: _run_engine(cfg, impl, _PROMPTS[:3], mode="overlap")
           for impl in ("fused", "gather")}
    assert got["fused"] == got["gather"]


# ---------------------------------------------------------------------------
# hybrid backend: registry, layout, serving smoke


def test_hybrid_registered_with_dual_key_layout():
    assert "hybrid" in list_backends()
    bk = get_backend("hybrid")
    assert bk.mode == "camformer"  # CAM decode path
    cfg = _cfg("hybrid")
    spec = bk.page_spec(cfg, n_pages=6, page_size=8, max_batch=2,
                        dtype=jnp.float32)
    # both key representations + the CAM temperature state
    for name in ("k_pages", "kp_pages", "v_pages", "k_scale"):
        assert name in spec, name
    sds, axes = spec["k_pages"]
    assert sds.shape == (6, cfg.n_kv_heads, 8, cfg.head_dim)
    assert axes == (None, "kv_heads", None, "head_dim")  # tp-shardable
    # bytes/token: packed keys + dense keys + dense values
    d, item = cfg.head_dim, 4
    assert (bk.cache_bytes_per_token(cfg, jnp.float32)
            == cfg.n_kv_heads * (d // 8 + 2 * d * item))


def test_hybrid_write_keeps_both_pools_current():
    """One _paged_write must land the same rows in the dense k_pages
    (flash prefill) and the packed kp_pages (CAM decode)."""
    cfg = _cfg("hybrid")
    bk = get_backend("hybrid")
    b, page, hkv, d = 1, 8, cfg.n_kv_heads, cfg.head_dim
    spec = bk.page_spec(cfg, 4, page, b, jnp.float32)
    pools = {n: jnp.zeros(sds.shape, sds.dtype)
             for n, (sds, _) in spec.items()}
    s = 4
    k = jax.random.normal(jax.random.PRNGKey(7), (b, hkv, s, d))
    v = jax.random.normal(jax.random.PRNGKey(8), (b, hkv, s, d))
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    pt = jnp.array([[2, 3]], jnp.int32)
    new = bk._paged_write(pools, k, v, pos, pt, jnp.array([s], jnp.int32),
                          cfg)
    # dense rows: exact K values at page 2, rows 0..s
    np.testing.assert_allclose(np.asarray(new["k_pages"][2, :, :s]),
                               np.asarray(k[0]), atol=1e-6)
    # packed rows: the sign bits of the same K
    from repro.core.bacam import unpack_bits

    unpacked = unpack_bits(new["kp_pages"][2, :, :s], d)
    assert jnp.array_equal(unpacked > 0, k[0] > 0)


# ---------------------------------------------------------------------------
# paged_io_stats: analytic fused/gather byte pins (satellite 3)


@pytest.mark.parametrize("backend", ["dense", "binary", "camformer",
                                     "hybrid"])
def test_paged_io_stats_pinned_against_pool_layout(backend):
    """The analytic decode/prefill read-byte columns, re-derived from
    the backend's OWN page_spec layout (deterministic measured bytes:
    pool row nbytes x rows touched) — the bench harness divides these
    by chunk size for its per-prefill-token artifact numbers."""
    cfg = _cfg(backend)
    bk = get_backend(backend)
    kv_len, page, n_table = 21, 8, 4
    dtype = jnp.float32
    io = bk.paged_io_stats(cfg, dtype, kv_len=kv_len, page_size=page,
                           n_table_pages=n_table)
    spec = bk.page_spec(cfg, 1, page, 1, dtype)  # layout probe: 1 page
    tok = {n: sds.size * jnp.dtype(sds.dtype).itemsize // page
           for n, (sds, _) in spec.items() if n.endswith("_pages")}
    live_rows = -(-kv_len // page) * page
    table_rows = n_table * page
    if backend in ("dense", "binary"):
        # binary pools store dense float K (binarized in-register at
        # attend time), so its accounting is the base dense one
        row = tok["k_pages"] + tok["v_pages"]
        assert io["fused_read_bytes"] == live_rows * row
        assert io["gather_read_bytes"] == table_rows * row
        assert io["prefill_fused_read_bytes"] == live_rows * row
        assert io["prefill_gather_read_bytes"] == table_rows * row
    else:
        # CAM decode: packed-key sweep + top-k value selection
        g = cfg.n_heads // cfg.n_kv_heads
        v_sel = (cfg.n_kv_heads * g * min(cfg.k_top, kv_len)
                 * cfg.head_dim * jnp.dtype(dtype).itemsize)
        assert io["fused_read_bytes"] == live_rows * tok["kp_pages"] + v_sel
        assert (io["gather_read_bytes"]
                == table_rows * tok["kp_pages"] + v_sel)
        dense_row = 2 * cfg.n_kv_heads * cfg.head_dim * 4
        if backend == "hybrid":
            # prefill chunks flash-read the dense pools
            assert tok["k_pages"] == dense_row // 2
            assert io["prefill_fused_read_bytes"] == live_rows * dense_row
            assert (io["prefill_gather_read_bytes"]
                    == table_rows * dense_row)
        else:
            # no fused Sq>1 CAM kernel yet: both prefill columns are
            # the gather numbers (the bench <= gate holds trivially)
            assert (io["prefill_fused_read_bytes"]
                    == io["prefill_gather_read_bytes"]
                    == table_rows * tok["kp_pages"] + v_sel)
    assert io["prefill_fused_read_bytes"] <= io["prefill_gather_read_bytes"]


def test_paged_io_stats_matches_bench_artifact_column():
    """The bench harness's kv_read_bytes_per_prefill_token column is
    exactly io[prefill_<impl>_read_bytes] * n_layers / chunk — pin the
    wiring so artifact numbers stay interpretable."""
    from benchmarks.paged_decode import bench_prefill_impl

    row = bench_prefill_impl("dense", max_batch=2, repeats=1)
    cfg = _cfg("dense")
    from repro.models.transformer import dtype_of

    io = get_backend("dense").paged_io_stats(
        cfg, dtype_of(cfg), kv_len=row["prompt_len"],
        page_size=row["prefill_slice"],
        n_table_pages=96 // row["prefill_slice"])
    for impl in ("fused", "gather"):
        want = (io[f"prefill_{impl}_read_bytes"] * cfg.n_layers
                / row["prefill_slice"])
        assert (row["lanes"][impl]["kv_read_bytes_per_prefill_token"]
                == want), impl
    assert row["fused_vs_gather_chunk_ticks"] > 0


# ---------------------------------------------------------------------------
# serving counters + gateway metrics (satellite 2)


def test_engine_prefill_counters_track_chunks():
    """prefill_tokens / prefill_ticks (the TTFT attribution pair): a
    24-token prompt at prefill_slice=8 is exactly 3 chunk ticks and 24
    prefill tokens; decode ticks leave both untouched.  A lone prefilling
    slot runs 1-row steps, so prefill_rows counts one per tick."""
    cfg = _cfg("dense")
    md = get_model_def(cfg)
    params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(md, cfg, params, max_batch=2, max_len=64, page_size=8,
                      mode="sync", prefill_slice=8)
    assert (eng.prefill_tokens, eng.prefill_ticks, eng.prefill_rows) == (
        0, 0, 0)
    eng.submit(Request(prompt=list(range(50, 74)),
                       sampling=SamplingParams(max_new=4), rid=0))
    eng.run()
    assert eng.prefill_tokens == 24
    assert eng.prefill_ticks == 3
    assert eng.prefill_rows == 3
    ticks_after = eng.prefill_ticks
    eng.submit(Request(prompt=[1, 2, 3],
                       sampling=SamplingParams(max_new=2), rid=1))
    eng.run()
    assert eng.prefill_tokens == 27  # short prompt: one 3-token chunk
    assert eng.prefill_ticks == ticks_after + 1
    assert eng.prefill_rows == 4


def test_gateway_metrics_exposes_prefill_counters():
    """GET /metrics carries the engine's prefill attribution next to the
    spec/preemption counters (no HTTP server needed: the handler's
    metrics dict is built by Gateway._metrics)."""
    from repro.serving.gateway import Gateway

    cfg = _cfg("dense")
    md = get_model_def(cfg)
    params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(md, cfg, params, max_batch=2, max_len=64, page_size=8,
                      mode="sync", prefill_slice=8)
    eng.submit(Request(prompt=list(range(40, 56)),
                       sampling=SamplingParams(max_new=2), rid=0))
    eng.run()
    gw = Gateway(eng)
    m = gw._metrics()
    assert m["engine"]["prefill_tokens"] == 16
    assert m["engine"]["prefill_ticks"] == 2
    assert m["engine"]["prefill_rows"] == 2  # one prefilling slot a tick
    assert "spec_proposed" in m["engine"]  # sits next to the spec stats
    assert "preemptions" in m["engine"]
