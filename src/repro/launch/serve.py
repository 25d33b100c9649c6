"""Serving driver: overlapped continuous batching with streamed outputs.

    PYTHONPATH=src python -m repro.launch.serve --arch camformer-bert --smoke \
        --requests 12 --max-new 24 [--backend camformer] \
        [--layer-backends dense,camformer] [--mode overlap|sync] \
        [--prefill-slice 64] [--temperature 0.8 --top-k 40 --top-p 0.95] \
        [--shared-prefix 32] [--no-stream]

Tokens print as they are generated (``engine.stream()``).  ``--mode
overlap`` (default) runs the dispatch-ahead loop — tick t+1 is enqueued
before tick t's tokens are read, so host scheduling overlaps the device
forward; ``--mode sync`` reads every tick (token-for-token identical).
``--prefill-slice N`` prefills joining prompts in N-token chunks across
ticks while resident slots keep decoding (continuous chunked-prefill
batching).  ``--shared-prefix N`` prepends a common N-token system prompt
to every request to exercise the copy-on-write prefix sharing (the
page-pool report shows the aliasing; the prefix stays LRU-retained after
the pool drains).
"""

import argparse
import sys

import jax

from repro.configs import get_config, smoke_config
from repro.launch.cli import add_backend_args, apply_backend_args
from repro.models import get_model_def
from repro.models.module import init_params
from repro.serving import Request, SamplingParams, ServeEngine, parse_faults
from repro.utils import compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    add_backend_args(ap)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="common system-prompt length prepended to every "
                         "request (exercises COW prefix sharing)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="paged-cache page size (camformer mode)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="page-pool size; default = full residency")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill chunk length (0 = whole prompt)")
    ap.add_argument("--mode", default="overlap", choices=("overlap", "sync"),
                    help="engine loop: dispatch-ahead overlap (default) or "
                         "read-every-tick sync")
    ap.add_argument("--prefill-slice", type=int, default=None,
                    help="continuous batching: prefill joining prompts in "
                         "chunks of this many tokens across ticks "
                         "(default: whole prompt in the admission tick)")
    ap.add_argument("--paged-impl", default=None,
                    choices=("fused", "gather"),
                    help="paged decode realization: fused Pallas "
                         "flash/CAM kernels (default) or the XLA "
                         "page-gather reference")
    ap.add_argument("--prefill-impl", default=None,
                    choices=("auto", "fused", "gather"),
                    help="Sq>1 chunk realization (chunked prefill and "
                         "speculative verify): fused paged flash kernel "
                         "or the XLA page-gather reference; 'auto' "
                         "(default) follows --paged-impl")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="self-speculative decoding: draft this many "
                         "tokens per tick with the binary stack and "
                         "verify k+1 positions in one fused target step "
                         "(0 = off, token-for-token plain decode)")
    ap.add_argument("--spec-backend", default=None,
                    help="drafter attention backend (default 'binary')")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: head-shard every page "
                         "pool over a tp-axis device mesh "
                         "(launch/mesh.py make_tp_mesh) and run the "
                         "fused tick shard_map-wide; 1 (default) is the "
                         "single-device engine, same code path, and any "
                         "degree is token-for-token identical to it")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission: submissions beyond this "
                         "queue depth raise QueueFullError (default: "
                         "unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline: requests not finished "
                         "within this many ms of submit end with "
                         "finish_reason='timeout'")
    ap.add_argument("--faults", default=None,
                    help="chaos fault plan, e.g. 'step.error@3,"
                         "kv.exhaust@1:4,tick.delay@0:20:p0.5:d0.01' "
                         "(serving/faults.py grammar; default: none)")
    ap.add_argument("--no-stream", action="store_true",
                    help="suppress per-token output, print only summaries")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = apply_backend_args(cfg, args)
    if args.prefill_chunk is not None:
        cfg = cfg.replace(prefill_chunk=args.prefill_chunk)
    md = get_model_def(cfg)
    params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
    faults = parse_faults(args.faults) if args.faults else None
    eng = ServeEngine(md, cfg, params, max_batch=args.max_batch,
                      max_len=args.max_len, page_size=args.page_size,
                      n_pages=args.n_pages, mode=args.mode,
                      prefill_slice=args.prefill_slice,
                      paged_impl=args.paged_impl,
                      prefill_impl=args.prefill_impl,
                      spec_k=args.spec_k, spec_backend=args.spec_backend,
                      tp=args.tp, max_queue=args.max_queue, faults=faults)
    layout = cfg.uniform_backend or ",".join(cfg.layer_backends)
    shard = (f", head-sharded tp={eng.tp} over {jax.device_count()} devices"
             if eng.tp > 1 else "")
    print(f"paged KV cache [{layout}]: {eng.kv.n_pages} pages x "
          f"{eng.kv.page_size} tokens "
          f"(page table {eng.kv.table.shape}{shard})")
    sampling = SamplingParams(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        max_new=args.max_new, deadline_ms=args.deadline_ms)
    rng = jax.random.PRNGKey(7)
    shared = list(range(1, args.shared_prefix + 1))
    for i in range(args.requests):
        rng, sub = jax.random.split(rng)
        plen = 4 + int(jax.random.randint(sub, (), 0, 12))
        prompt = shared + list(
            map(int, jax.random.randint(sub, (plen,), 0, cfg.vocab)))
        eng.submit(Request(prompt=prompt, sampling=sampling, rid=i))
    import time as _time
    t0 = _time.perf_counter()
    for out in eng.stream():
        if not args.no_stream:
            tail = f"  [{out.finish_reason}]" if out.finished else ""
            print(f"  req {out.rid} #{out.index}: {out.token}{tail}")
    wall = _time.perf_counter() - t0
    print(f"[{args.mode}] {eng.ticks} decode ticks in {wall:.2f}s "
          f"({eng.ticks / max(wall, 1e-9):.1f} ticks/s), "
          f"{eng.readbacks} readbacks, host idle "
          f"{eng.blocked_s / max(wall, 1e-9):.0%}")
    if eng.tick_errors:
        print(f"{'chaos' if faults else 'ERROR'}: {eng.tick_errors} tick "
              f"errors contained (last: {eng.last_error}), "
              f"{eng.sched.timeouts} timeouts, "
              f"{eng.sched.rejections} rejections")
    if eng.spec_k:
        print(f"speculation: k={eng.spec_k}, "
              f"{eng.spec_accepted}/{eng.spec_proposed} drafts accepted "
              f"({eng.spec_acceptance:.0%})")
    print(f"peak pool residency: {eng.peak_pages}/{eng.kv.n_pages - 1} pages"
          f" ({eng.kv.shared_pages} still shared, "
          f"{eng.kv.retained_pages} prefix pages retained at drain)")
    for r in sorted(eng.done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt[{len(r.prompt)}] "
              f"prefix_hit={r.prefix_matched} -> {r.tokens}")
    if eng.tick_errors and not faults:
        sys.exit(1)  # device ticks failed with no fault plan: a real fault


if __name__ == "__main__":
    main()
