"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, lowers the right step
function (train_step / prefill_step / serve_step per shape kind) with
explicit in/out shardings, compiles it, prints memory_analysis() and
cost_analysis(), and extracts loop-aware roofline terms from the optimized
HLO into results/dryrun/*.json (consumed by benchmarks/roofline.py and
EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-110b \
      --shape train_4k [--multi-pod] [--attn-mode camformer] [--all]
"""

# The placeholder-device flag MUST precede any jax import (device count is
# locked at first backend init).  Do NOT set this anywhere global.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config  # noqa: E402
from repro.launch import analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (batch_specs, cache_specs_trees,  # noqa: E402
                                make_prefill_step, make_serve_step,
                                make_train_step, state_specs)
from repro.models import get_model_def  # noqa: E402
from repro.models.module import count_params  # noqa: E402
from repro.sharding.partitioning import ACT_RULES, resolve_spec  # noqa: E402
from repro.utils.hlo import analyze_hlo  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../results/dryrun")

# Full-attention archs skip *dense* long_500k (quadratic-prefill families,
# per the assignment) but run it with the paper's technique: binary packed-K
# cache + top-32 sparse V gather makes 500k-decode tractable (DESIGN.md §4).
ATTENTION_FAMILIES = ("dense", "moe", "vlm", "audio")


def effective_config(arch: str, shape: str, backend: str | None,
                     dist_topk: bool = False, prefill_chunk: int = 0):
    cfg = get_config(arch)
    if dist_topk:
        cfg = cfg.replace(distributed_topk=True)
    if prefill_chunk:
        cfg = cfg.replace(prefill_chunk=prefill_chunk)
    note = ""
    if backend:
        cfg = cfg.replace(attn_backend=backend)
        note = f"backend={backend} (CLI)"
    elif shape == "long_500k" and cfg.family in ATTENTION_FAMILIES:
        cfg = cfg.replace(attn_backend="camformer")
        note = ("dense long_500k skipped (full attention); run with "
                "CAMformer binary top-k cache per paper Sec. IV-C")
    return cfg, note


def build_cell(arch: str, shape: str, mesh, backend: str | None,
               dist_topk: bool = False, prefill_chunk: int = 0):
    cfg, note = effective_config(arch, shape, backend, dist_topk,
                                 prefill_chunk)
    md = get_model_def(cfg)
    kind = SHAPES[shape]["kind"]
    sh = SHAPES[shape]
    n_params = count_params(md.specs(cfg))

    if kind == "train":
        from repro.launch.steps import METRIC_KEYS

        step, _ = make_train_step(md, cfg)
        state_sds, state_shard = state_specs(md, cfg, mesh)
        b_sds, b_shard = batch_specs(cfg, shape, mesh)
        metrics_shard = {k: NamedSharding(mesh, P()) for k in METRIC_KEYS}
        fn = jax.jit(step, in_shardings=(state_shard, b_shard),
                     out_shardings=(state_shard, metrics_shard),
                     donate_argnums=(0,))
        args = (state_sds, b_sds)
    elif kind == "prefill":
        from repro.launch.steps import params_specs

        step = make_prefill_step(md, cfg)
        p_sds, p_serve_shard = params_specs(md, cfg, mesh, serve=True)
        p_shard = {"params": p_serve_shard}
        b_sds, b_shard = batch_specs(cfg, shape, mesh)
        c_sds, c_shard = cache_specs_trees(md, cfg, sh["global_batch"],
                                           sh["seq_len"], mesh)
        logits_shard = NamedSharding(mesh, resolve_spec(
            ("batch", "vocab"), (sh["global_batch"], cfg.vocab), mesh,
            ACT_RULES))
        fn = jax.jit(step,
                     in_shardings=(p_shard["params"], b_shard, c_shard),
                     out_shardings=(logits_shard, c_shard),
                     donate_argnums=(2,))
        args = (p_sds, b_sds, c_sds)
    else:  # decode
        from repro.launch.steps import params_specs

        step = make_serve_step(md, cfg)
        p_sds_only, p_serve_shard = params_specs(md, cfg, mesh, serve=True)
        p_sds = {"params": p_sds_only}
        p_shard_all = {"params": p_serve_shard}
        b_sds, b_shard = batch_specs(cfg, shape, mesh, serve=True)
        c_sds, c_shard = cache_specs_trees(md, cfg, sh["global_batch"],
                                           sh["seq_len"], mesh)
        logits_shard = NamedSharding(mesh, resolve_spec(
            ("batch", "vocab"), (sh["global_batch"], cfg.vocab), mesh,
            ACT_RULES))
        fn = jax.jit(step,
                     in_shardings=(p_shard_all["params"], b_shard["tokens"],
                                   b_shard["pos"], b_shard["kv_len"], c_shard),
                     out_shardings=(logits_shard, c_shard),
                     donate_argnums=(4,))
        args = (p_sds["params"], b_sds["tokens"], b_sds["pos"],
                b_sds["kv_len"], c_sds)
    return cfg, md, fn, args, n_params, note


def run_cell(arch: str, shape: str, *, multi_pod: bool, backend=None,
             out_dir=RESULTS_DIR, tag="", dist_topk=False, prefill_chunk=0):
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    cfg, md, fn, args, n_params, note = build_cell(arch, shape, mesh,
                                                   backend, dist_topk,
                                                   prefill_chunk)
    # the ambient mesh for constrain(), for this cell only
    with jax.set_mesh(mesh), mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
    print(f"[{arch} x {shape} x {'multipod' if multi_pod else 'pod'}] "
          f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
    print("  memory_analysis:", mem)
    print("  cost_analysis: flops=%.3e bytes=%.3e"
          % (cost.get("flops", 0), cost.get("bytes accessed", 0)))
    hlo = analyze_hlo(compiled.as_text())
    roof = analysis.roofline_terms(hlo, cfg, shape, n_params, chips)
    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    rec = {
        "arch": arch, "shape": shape, "kind": SHAPES[shape]["kind"],
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "backend": cfg.backend,
        "note": note, "tag": tag,
        "profile": __import__("repro.sharding.partitioning",
                              fromlist=["x"]).get_parallelism_profile(),
        "n_params": n_params,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_total": per_dev_bytes,
        },
        "xla_cost_analysis": {
            "flops_unrolled": cost.get("flops", 0.0),
            "bytes_accessed_unrolled": cost.get("bytes accessed", 0.0),
        },
        "hlo": {
            "flops_per_device": hlo["flops"],
            "dot_bytes_per_device": hlo["dot_bytes"],
            "collective_bytes_per_device": hlo["collective_bytes"],
            "collectives": hlo["collectives"],
            "loop_multipliers": {k: v for k, v in
                                 sorted(hlo["loop_multipliers"].items())[:12]},
        },
        "roofline": roof,
    }
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}_{shape}_{'multipod' if multi_pod else 'pod'}"
    name += f"_{tag}" if tag else ""
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=2, default=float)
    print(f"  roofline: compute {roof['compute_s']:.3e}s | memory "
          f"{roof['memory_s']:.3e}s | collective {roof['collective_s']:.3e}s "
          f"-> {roof['dominant']}-bound, roofline fraction "
          f"{roof['roofline_fraction']:.3f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    from repro.launch.cli import add_backend_args, resolve_backend_arg
    add_backend_args(ap, choices=[None, "dense", "binary", "camformer"],
                     layer_policy=False)  # scan-compiled cells are uniform
    ap.add_argument("--tag", default="")
    ap.add_argument("--profile", default="tp", choices=["tp", "dp"],
                    help="sharding profile (see sharding/partitioning.py)")
    ap.add_argument("--dist-topk", action="store_true",
                    help="distributed two-stage CAM search (shard_map)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill (tokens per chunk; 0 = whole-seq)")
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch x shape) cell")
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    from repro.sharding.partitioning import set_parallelism_profile
    set_parallelism_profile(args.profile)

    backend = resolve_backend_arg(args)
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for shape in SHAPES:
                try:
                    run_cell(arch, shape, multi_pod=args.multi_pod,
                             backend=backend, out_dir=args.out_dir,
                             tag=args.tag)
                except Exception as e:  # noqa: BLE001 — report and continue
                    print(f"[{arch} x {shape}] FAILED: {type(e).__name__}: {e}")
        return
    run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
             backend=backend, out_dir=args.out_dir, tag=args.tag,
             dist_topk=args.dist_topk, prefill_chunk=args.prefill_chunk)


if __name__ == "__main__":
    main()
