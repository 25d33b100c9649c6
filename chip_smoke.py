#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: camformer-bert at full width.

    python3 chip_smoke.py [--seed N]       # one chip
    python3 chip_smoke.py --tp 4 [--seed N]  # four chips: the tp phase only

Drives ``ServeEngine`` -> fused device step -> Pallas paged kernels the
way a user would (``get_config`` / ``get_model_def`` / ``init_params`` /
``ServeEngine`` / ``submit`` / ``stream`` / ``run``) with random bf16
weights made from ``--seed``, and checks what comes out:

  * kernels: the compiled ``bacam_paged_topk_stage1`` equals
    ``kernels/ref.bacam_paged_topk_ref`` exactly (integer outputs), and
    the compiled ``paged_flash_decode`` (Sq=1 and Sq>1) matches a
    ``paged_gather_ref`` gather plus masked attend within ``FLASH_TOL``;
  * engines: a camformer and a dense engine (paged_impl="fused",
    max_batch 8, max_len 1024, page 64; the dense one with
    prefill_slice=256 so the Sq>1 flash kernel runs) each serve 8 greedy
    requests with prompts of 64-896 tokens and max_new 32.  Every request
    must finish with "length" and 32 tokens, no tick may fail, the
    compiled decode step must hold the engine's kernel as a
    tpu_custom_call, and mode="sync" must give the same token streams as
    mode="overlap".

``--tp 4`` runs only the tensor-parallel phase: the same camformer
requests at tp=1 and tp=4 in this process must give bit-identical token
streams, with the page pools spread over four devices.

Times printed are those of a smoke run (compilation included), not
metrics.  The last line of standard output is the result,
``{"ok": true, "device": {...}}``; it is printed only when every check
passed.  With no TPU the script exits non-zero: there is no CPU fallback.
Everything runs in this one process, which holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MODEL = "camformer-bert"
MAX_BATCH, MAX_LEN, PAGE = 8, 1024, 64
N_REQUESTS, MAX_NEW = 8, 32
PROMPT_LENS = (64, 896)
PREFILL_SLICE = 256  # dense engine: Sq>1 chunks through the flash kernel

# Flash kernel vs the float32 gather reference, computed at "highest"
# matmul precision (max |difference| over live rows).  The pools hold bf16,
# so K and V enter both sides exactly, but the kernel's dots run at
# Mosaic's default precision, which on the chip behaves as one bf16 pass of
# the MXU: the float32 query rows and softmax probabilities are rounded to
# bf16 (relative 2**-9).  On outputs of size ~1 (convex combinations of
# N(0, 1) V rows) that moves the result by a few 1e-3: on a TPU v5e at
# these shapes and seed 0, the largest differences were 1.5e-3 (Sq=1) and
# 4.6e-3 (Sq=256, 2M outputs).  1e-2 admits that; the kernel phase also
# plants a wrong page in every live slot's table and checks that the
# difference it makes exceeds the tolerance.  Masking is pinned exactly by
# the interpret-mode tests on the CPU.
FLASH_TOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit
    compiles nothing and adds nothing)."""

    def __init__(self, jax):
        self.secs = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


class Phase:
    def __init__(self, name, clock):
        self.name, self.clock = name, clock

    def __enter__(self):
        print(f"[{self.name}]")
        self.t0, self.c0 = time.perf_counter(), self.clock.secs
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            print(f"  smoke timing (not a metric): wall "
                  f"{time.perf_counter() - self.t0:.1f} s, of which "
                  f"compile {self.clock.secs - self.c0:.1f} s")


# ---------------------------------------------------------------------------
# kernels against their references, compiled, at camformer-bert's widths


def kernel_phase(seed, cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.attention import AttentionSpec, attention
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    from repro.kernels.bacam_decode import bacam_paged_topk_stage1

    rng = np.random.default_rng(seed)
    b, hkv, d = MAX_BATCH, cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // cfg.n_kv_heads
    np_ = MAX_LEN // PAGE
    n_pages = 1 + b * np_
    pt = jnp.asarray(rng.permutation(n_pages - 1)[:b * np_].reshape(b, np_)
                     + 1, jnp.int32)
    # slot 0 is inert (kv_len 0), slot 1 ends on a page boundary, slot 2
    # fills the table; the rest are random
    kv_len = rng.integers(1, MAX_LEN + 1, b)
    kv_len[:3] = (0, 5 * PAGE, MAX_LEN)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    q_pos = jnp.maximum(kv_len - 1, 0)

    # --- CAM association: exact equality, values AND indices ---
    w = d // 32
    qp = jnp.asarray(rng.integers(0, 2**32, (b, hkv, g, w), dtype=np.uint32))
    kp = jnp.asarray(rng.integers(0, 2**32, (n_pages, hkv, PAGE, w),
                                  dtype=np.uint32))
    args = dict(d=d, group=cfg.group_size, stage1_k=cfg.stage1_k)
    vals, idx = bacam_paged_topk_stage1(qp, kp, pt, kv_len, kv_len - 1,
                                        interpret=False, **args)
    rv, ri = kref.bacam_paged_topk_ref(
        qp, kp, pt, kv_len, d, q_pos=kv_len - 1,
        group_size=cfg.group_size, stage1_k=cfg.stage1_k)
    check(vals.shape == rv.shape and bool((vals == rv).all())
          and bool((idx == ri).all()),
          f"compiled bacam_paged_topk_stage1 == bacam_paged_topk_ref "
          f"exactly, {vals.shape} candidates")

    # --- flash: Sq=1 (dense and binary) and Sq>1 against the gather ---
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    k_pages = jax.random.normal(keys[0], (n_pages, hkv, PAGE, d),
                                jnp.bfloat16)
    v_pages = jax.random.normal(keys[1], (n_pages, hkv, PAGE, d),
                                jnp.bfloat16)
    ck = kref.paged_gather_ref(k_pages, pt).astype(jnp.float32)
    cv = kref.paged_gather_ref(v_pages, pt).astype(jnp.float32)
    kv_pos = jnp.arange(ck.shape[2], dtype=jnp.int32)[None]

    def reference(q, kk, qpos, kvl):
        with jax.default_matmul_precision("highest"):
            return attention(
                q.astype(jnp.float32), kk, cv, AttentionSpec(mode="dense"),
                causal=True, q_positions=qpos, kv_positions=kv_pos,
                kv_valid=kv_pos < kvl.reshape(-1, 1))

    live = np.asarray(kv_len) > 0
    q1 = jax.random.normal(keys[2], (b, hkv * g, 1, d), jnp.float32)
    sgn = lambda x: jnp.where(x > 0, 1.0, -1.0)
    for binary in (False, True):
        got = kops.paged_flash_decode(q1, k_pages, v_pages, pt, kv_len,
                                      q_pos, binary=binary, interpret=False)
        if binary:  # HAD sign scoring: the reference scores sign(q).sign(k)
            want = reference(sgn(q1), sgn(ck), q_pos.reshape(-1, 1), kv_len)
        else:
            want = want_dense = reference(q1, ck, q_pos.reshape(-1, 1),
                                          kv_len)
        err = float(jnp.abs(got - want)[live].max())
        check(err <= FLASH_TOL and bool((got[~live] == 0).all()),
              f"compiled paged_flash_decode Sq=1 binary={binary}: max "
              f"|diff| {err:.3g} <= {FLASH_TOL} vs gather+attend")

    # the tolerance must see a wrong page: every live slot reads another
    # slot's first page
    bad = pt.at[:, 0].set(jnp.roll(pt[:, 0], 1))
    got = kops.paged_flash_decode(q1, k_pages, v_pages, bad, kv_len, q_pos,
                                  interpret=False)
    per_slot = jnp.abs(got - want_dense).reshape(b, -1).max(-1)[live]
    moved = float(per_slot.min())
    check(moved > FLASH_TOL,
          f"a planted wrong page moves every live slot's output by at "
          f"least {moved:.3g} > {FLASH_TOL}")

    sq = PREFILL_SLICE
    start = jnp.asarray(rng.integers(0, MAX_LEN - sq + 1, b), jnp.int32)
    start = start.at[0].set(0)
    kvl = start + sq
    qs = jax.random.normal(keys[3], (b, hkv * g, sq, d), jnp.float32)
    got = kops.paged_flash_prefill(qs, k_pages, v_pages, pt, kvl, start,
                                   interpret=False)
    want = reference(qs, ck, start[:, None] + jnp.arange(sq)[None], kvl)
    err = float(jnp.abs(got - want).max())
    check(err <= FLASH_TOL,
          f"compiled paged_flash_decode Sq={sq}: max |diff| {err:.3g} <= "
          f"{FLASH_TOL} vs gather+attend")


# ---------------------------------------------------------------------------
# the serving engine, end to end


def make_requests(seed, vocab):
    import numpy as np

    from repro.serving import Request, SamplingParams

    rng = np.random.default_rng(seed + 1)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    greedy = SamplingParams(temperature=0.0, max_new=MAX_NEW)
    return [Request(prompt=[int(t) for t in rng.integers(0, vocab, n)],
                    sampling=greedy, rid=i) for i, n in enumerate(lens)]


def watch_decode_step(eng):
    """Record the argument shapes of the engine's greedy decode step (its
    jit table is private) and return a function that compiles that step
    again and gives its HLO text; the compile cache serves it."""
    import jax

    step = eng._decode_jit(False)
    seen = []

    def watched(*a):
        if not seen:
            seen.append(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), a))
        return step(*a)

    eng._decode_jits[False] = watched
    return lambda: step.lower(*seen[0]).compile().as_text()


def serve(md, cfg, params, *, mode, seed, tp=1, prefill_slice=None,
          kernel=None):
    """Serve the smoke requests on a fresh engine; returns (streams,
    engine).  overlap drives stream(), sync drives run()."""
    from repro.serving import ServeEngine

    eng = ServeEngine(md, cfg, params, max_batch=MAX_BATCH,
                      max_len=MAX_LEN, page_size=PAGE, mode=mode,
                      prefill_slice=prefill_slice, paged_impl="fused",
                      tp=tp)
    hlo = watch_decode_step(eng) if kernel else None
    reqs = make_requests(seed, cfg.vocab)
    for r in reqs:
        eng.submit(r)
    if mode == "overlap":
        n_events = sum(1 for _ in eng.stream())
    else:
        n_events = sum(len(r.tokens) for r in eng.run())
    streams = {r.rid: list(r.tokens) for r in reqs}
    print(f"  {cfg.uniform_backend}/{mode} tp={tp}: {len(reqs)} requests "
          f"(prompts {sorted(len(r.prompt) for r in reqs)}), "
          f"{n_events} tokens, {eng.ticks} decode ticks")
    check(all(r.finish_reason == "length" and len(r.tokens) == MAX_NEW
              for r in reqs),
          f"every request finished with 'length' after {MAX_NEW} tokens")
    check(eng.tick_errors == 0, f"tick_errors == 0 (last: {eng.last_error})")
    if kernel:
        txt = hlo()
        calls = [ln for ln in txt.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        check(any(kernel in ln for ln in calls),
              f"compiled decode step holds {kernel} as a tpu_custom_call")
    return streams, eng


def engine_phase(md, cfg, params, seed, backend, clock):
    kernel = {"camformer": "bacam_paged_topk_stage1",
              "dense": "paged_flash_decode"}[backend]
    cfg = cfg.replace(attn_backend=backend)
    slice_ = PREFILL_SLICE if backend == "dense" else None
    with Phase(f"engine {backend}, overlap", clock):
        over, _ = serve(md, cfg, params, mode="overlap", seed=seed,
                        prefill_slice=slice_, kernel=kernel)
    with Phase(f"engine {backend}, sync", clock):
        sync, _ = serve(md, cfg, params, mode="sync", seed=seed,
                        prefill_slice=slice_)
    check(sync == over, f"{backend}: sync and overlap token streams are "
          "identical")


def tp_phase(md, cfg, params, seed, tp, clock):
    import jax

    with Phase("tp=1 reference", clock):
        ref, _ = serve(md, cfg, params, mode="overlap", seed=seed)
    with Phase(f"tp={tp}", clock):
        got, eng = serve(md, cfg, params, mode="overlap", seed=seed, tp=tp)
    check(got == ref, f"tp={tp} token streams are bit-identical to tp=1")
    leaves = jax.tree.leaves(eng.caches)
    spread = [len({s.device for s in leaf.addressable_shards})
              for leaf in leaves]
    check(all(n == tp for n in spread),
          f"all {len(leaves)} pool leaves have shards on {tp} distinct "
          f"devices")
    # a replicated pool also has a shard on every device: each shard must
    # hold n_kv_heads / tp heads and every other axis whole
    hkv = cfg.n_kv_heads
    split = []
    for leaf in leaves:
        shard = leaf.sharding.shard_shape(leaf.shape)
        cut = [(full, part) for full, part in zip(leaf.shape, shard)
               if full != part]
        split.append(cut == [(hkv, hkv // tp)])
    check(all(split), f"each pool leaf is split {tp} ways on its kv-head "
          f"axis ({hkv} -> {hkv // tp} heads a shard), whole on the others")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel phase at this "
                         "degree (needs that many chips)")
    args = ap.parse_args()

    try:
        from repro.utils import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    print(f"compile cache: {compile_cache.enable()}")
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}); there is no CPU fallback",
              file=sys.stderr)
        return 1

    clock = CompileClock(jax)
    try:
        import jax.numpy as jnp

        from repro.configs import get_config
        from repro.models import get_model_def
        from repro.models.module import count_params, init_params

        cfg = get_config(MODEL)
        md = get_model_def(cfg)
        with Phase(f"init {MODEL}", clock):
            # one jitted init: eager init compiles once per leaf shape
            params = jax.jit(lambda key: init_params(
                md.specs(cfg), key, dtype=jnp.bfloat16))(
                    jax.random.PRNGKey(args.seed))
            jax.block_until_ready(params)
            print(f"  {MODEL}: {cfg.n_layers} layers x d_model "
                  f"{cfg.d_model}, {cfg.n_heads} heads x {cfg.head_dim}, "
                  f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
                  f"{count_params(md.specs(cfg)) / 1e9:.3f} B params, "
                  f"bf16 weights from seed {args.seed}")
        if args.tp > 1:
            if len(devs) < args.tp:
                raise SmokeFailure(f"--tp {args.tp} needs {args.tp} chips, "
                                   f"found {len(devs)}")
            tp_phase(md, cfg, params, args.seed, args.tp, clock)
        else:
            with Phase("kernels vs references", clock):
                kernel_phase(args.seed, cfg)
            for backend in ("camformer", "dense"):
                engine_phase(md, cfg, params, args.seed, backend, clock)
    except Exception as e:  # any failed phase fails the run
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
