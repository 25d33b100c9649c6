"""Gateway launcher: serve a model over HTTP/SSE.

    PYTHONPATH=src python -m repro.launch.gateway --arch camformer-bert \\
        --smoke [--backend camformer] [--host 127.0.0.1 --port 8000] \\
        [--max-batch 8 --max-len 256] [--mode overlap|sync] \\
        [--prefill-slice 64] [--paged-impl fused|gather]

Then point traffic at it:

    curl -N -X POST http://127.0.0.1:8000/v1/generate \\
        -d '{"prompt": [3, 5, 8, 1], "max_new": 16, "temperature": 0.8}'
    curl http://127.0.0.1:8000/healthz
    curl http://127.0.0.1:8000/metrics

Each generated token streams back as a server-sent event; closing the
connection mid-stream cancels the request and frees its pages.  See
``benchmarks/serve_slo.py`` for the Poisson load generator that drives
this endpoint (or the engine in-process) and reports TTFT/TPOT
percentiles and goodput-under-SLO.
"""

import argparse
import asyncio

import jax

from repro.configs import get_config, smoke_config
from repro.launch.cli import add_backend_args, apply_backend_args
from repro.models import get_model_def
from repro.models.module import init_params
from repro.serving import ServeEngine, parse_faults
from repro.serving.gateway import Gateway
from repro.utils import compile_cache


def build_engine(args) -> ServeEngine:
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = apply_backend_args(cfg, args)
    if args.prefill_chunk is not None:
        cfg = cfg.replace(prefill_chunk=args.prefill_chunk)
    md = get_model_def(cfg)
    params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
    faults = parse_faults(args.faults) if getattr(args, "faults", None) else None
    return ServeEngine(
        md,
        cfg,
        params,
        max_batch=args.max_batch,
        max_len=args.max_len,
        page_size=args.page_size,
        n_pages=args.n_pages,
        mode=args.mode,
        prefill_slice=args.prefill_slice,
        paged_impl=args.paged_impl,
        prefill_impl=args.prefill_impl,
        spec_k=args.spec_k,
        spec_backend=args.spec_backend,
        tp=args.tp,
        max_queue=getattr(args, "max_queue", None),
        faults=faults,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    add_backend_args(ap)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000, help="0 = pick a free port")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--n-pages", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--mode", default="overlap", choices=("overlap", "sync"))
    ap.add_argument(
        "--prefill-slice",
        type=int,
        default=None,
        help="continuous batching: prefill joining prompts in chunks of "
        "this many tokens across ticks",
    )
    ap.add_argument("--paged-impl", default=None, choices=("fused", "gather"))
    ap.add_argument(
        "--prefill-impl",
        default=None,
        choices=("auto", "fused", "gather"),
        help="Sq>1 chunk realization (chunked prefill / speculative "
        "verify): 'auto' follows --paged-impl",
    )
    ap.add_argument(
        "--spec-k",
        type=int,
        default=None,
        help="self-speculative decoding: binary-stack drafts per tick, "
        "verified k+1 at a time in one fused target step (0 = off)",
    )
    ap.add_argument(
        "--spec-backend",
        default=None,
        help="drafter attention backend (default 'binary')",
    )
    ap.add_argument(
        "--max-queue",
        type=int,
        default=None,
        help="bounded admission: beyond this queue depth new requests get "
        "HTTP 429 + Retry-After (default: unbounded)",
    )
    ap.add_argument(
        "--faults",
        default=None,
        help="chaos fault plan, e.g. 'step.error@3,kv.exhaust@1:4' "
        "(serving/faults.py grammar; default: none)",
    )
    ap.add_argument(
        "--tp",
        type=int,
        default=1,
        help="tensor-parallel degree: head-shard the page pools over a "
        "tp-axis device mesh (launch/mesh.py make_tp_mesh); 1 = the "
        "single-device engine, same code path",
    )
    args = ap.parse_args()
    compile_cache.enable()

    engine = build_engine(args)
    layout = engine.cfg.uniform_backend or ",".join(engine.cfg.layer_backends)

    async def serve() -> None:
        gw = Gateway(engine, host=args.host, port=args.port)
        await gw.start()
        shard = f", head-sharded tp={engine.tp}" if engine.tp > 1 else ""
        print(
            f"gateway [{layout}] listening on http://{args.host}:{gw.port} "
            f"(pool {engine.kv.n_pages - 1} pages x {engine.kv.page_size} "
            f"tokens, {args.mode} loop{shard})"
        )
        try:
            await gw.serve_forever()
        except asyncio.CancelledError:
            pass
        finally:
            await gw.aclose()

    try:
        asyncio.run(serve())
    except KeyboardInterrupt:
        print("gateway stopped")


if __name__ == "__main__":
    main()
