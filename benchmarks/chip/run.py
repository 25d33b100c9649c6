#!/usr/bin/env python3
"""Serving benchmark on the chip, one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n>
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
their files, and the weights module and reference the configuration
names, are found by name (harness/cell.py).  One process: weights made
on the device from the seed by that module, ``ServeEngine`` behind the
``EngineRunner`` engine thread, every step shape warmed, the first wave
of a closed loop prefilled, then a window of ``--seconds`` in which
clients send the mix and time each token where they receive it.  After
the window the program's state is freed and a sample of the greedy
requests goes through the plain reference (harness/check.py).

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window and from the
engine's counters.  The last line of
standard output is the result object; the numbers compared come last in
it (``checks``) and on the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(REPO, "src"))

FIRST_WAVE_TIMEOUT = 600.0
RUNS_DIR = os.path.join(HERE, "_runs")


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Counts and sums JAX's backend compilations (a persistent-cache hit
    compiles nothing and adds nothing)."""

    def __init__(self, jax):
        self.n, self.secs = 0, 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.secs += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _warm(engine, vocab: int, sampling: dict, slice_: int) -> None:
    """Run the step variants the window uses: the prefill step with and
    without sampling (a chunk that is not a prompt's last never samples),
    and the decode step greedy-only and sampled."""
    from repro.serving import Request, SamplingParams

    n = slice_ + 1 if slice_ else 17
    for start, sp in enumerate((SamplingParams(temperature=0.0, max_new=3),
               SamplingParams(temperature=sampling["temperature"],
                              top_k=sampling["top_k"],
                              top_p=sampling.get("top_p", 1.0), max_new=3))):
        # prompts that share no page: each request prefills on its own
        req = Request(prompt=[(start * n + i) % vocab for i in range(n)],
                      sampling=sp)
        for _ in engine.stream(req):
            pass


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, overrides: dict | None = None,
             control: bool = False, log=sys.stderr) -> dict:
    """One run of a cell; returns the result object (and, for the
    calibration script, ``_check`` with every reading)."""
    from harness import cell as C

    c = C.resolve(C.load_benchmark(), workload)
    conf = _merge(c.config, (overrides or {}).get("config", {}))
    mix = _merge(c.traffic, (overrides or {}).get("traffic", {}))
    model, eng_cfg = conf["model"], conf["engine"]

    from repro.utils import compile_cache

    print(f"compile cache: {compile_cache.enable()}", file=log)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if conf.get("matmul_precision"):
        # the precision the configuration states, before anything traces
        jax.config.update("jax_default_matmul_precision",
                          conf["matmul_precision"])
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", file=log)
    if require_chip and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, found platform {dev.platform!r}; "
                     "there is no CPU fallback")
    if len(devs) < c.chips:
        raise NoChip(f"cell {workload} needs {c.chips} chips, found "
                     f"{len(devs)}")
    if require_chip:
        from harness import counts

        counts.peaks(dev.device_kind)  # unknown device: error, before work
    clock = CompileClock(jax)

    from harness import check, serve, tracefile, traffic
    from harness.window import Window, counters, p95
    from repro.configs.base import ModelConfig
    from repro.models import get_model_def
    from repro.serving import ServeEngine
    from repro.serving.gateway import EngineRunner

    cfg = ModelConfig(**model)
    md = get_model_def(cfg)
    W = C.weights(conf["weights"])
    params = W.program_params(model, seed)
    jax.block_until_ready(params)
    engine = ServeEngine(
        md, cfg, params, seed=seed, max_batch=eng_cfg["max_batch"],
        max_len=eng_cfg["max_len"], page_size=eng_cfg["page_size"],
        n_pages=eng_cfg.get("n_pages"), mode=eng_cfg["mode"],
        prefill_slice=eng_cfg.get("prefill_slice"),
        paged_impl=eng_cfg["paged_impl"], tp=eng_cfg.get("tp", 1))
    del params
    _warm(engine, model["vocab"], mix["sampling"],
          eng_cfg.get("prefill_slice"))
    print(f"warm: {clock.n} compilations, {clock.secs:.1f} s", file=log)

    gen = traffic.Traffic(mix, seed, model["vocab"])
    runner = EngineRunner(engine, idle_wait_s=0.001)
    clients = serve.Clients(runner, gen)
    runner.start()
    wave = clients.start_closed(gen.first_wave())
    if not serve.wait_until(lambda: all(r.times or r.finish for r in wave),
                            FIRST_WAVE_TIMEOUT):
        raise RuntimeError("the first wave was not prefilled in "
                           f"{FIRST_WAVE_TIMEOUT} s")

    compiles0 = clock.n
    t0 = time.perf_counter()
    t1 = t0 + seconds
    setup_s = t0 - T_START

    snap = [counters(engine)]
    trace_dir = os.path.join(RUNS_DIR, f"trace-{workload}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracefile.WINDOW):
        while time.perf_counter() < t1:
            time.sleep(0.005)
        snap.append(counters(engine))
    if trace:
        jax.profiler.stop_trace()
    clients.stop()
    compiles = clock.n - compiles0
    runner.stop()
    tick_errors = engine.tick_errors
    records = list(clients.records)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:max(c.chips, 1)])
    print(f"window: {seconds} s, {compiles} compilations inside it; "
          f"setup {setup_s:.2f} s; tick_errors {tick_errors}; "
          f"memory peak {peak} bytes", file=log)
    print("engine in the window: " + ", ".join(
        f"{k} {snap[-1][k] - snap[0][k]}" for k in snap[0]), file=log)
    del runner, clients, engine
    gc.collect()

    # -- end-to-end statistics, from the client side ----------------------
    full = Window(t0=t0, t1=t1, records=records, before=snap[0],
                  after=snap[-1], model=model, engine=eng_cfg,
                  device_kind=dev.device_kind,
                  matmul_weights_per_token=W.matmul_weights_per_token(model))
    live = [r for r in records if r.due < t1]
    failed = [r for r in live if r.finish in ("error", "rejected", "timeout")]
    e2e = {}
    for m in c.end_to_end:
        name = m["name"]
        if name == "setup_s":
            val = setup_s
        elif name == "tokens_per_s":
            val = sum(1 for _ in full.tokens()) / seconds
        elif name == "itl_p95_ms":
            val = p95(full.token_gaps_ms())
        else:
            raise KeyError(f"end-to-end metric {name!r} has no definition "
                           "in run.py")
        if val is not None:
            e2e[name] = {"value": val, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": len(live),
              "failed": len(failed)}
    breakdown = None
    if trace:
        path = tracefile.find(trace_dir)
        summary = tracefile.reduce(path)
        full.trace = summary
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[k, v] for k, v in ops],
                     "idle_gaps": [[k, v] for k, v in summary.gaps]}
        metrics = {}
        for m in c.per_layer:
            val = C.reader(m["name"])(full)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        metrics = e2e

    # -- correctness: the plain reference over the served tokens ----------
    corr = conf["correct"]
    ref_mod = C.reference(conf["reference"])
    ref = ref_mod.Reference(
        model, seed, t_pad=eng_cfg["max_len"], n_seq=corr["sample_requests"],
        prefill_slice=eng_cfg.get("prefill_slice") or eng_cfg["max_len"],
        control=corr["control"] if control else None, weights=W)
    t_ref = time.perf_counter()
    got = check.compare(ref, records, seed, corr["sample_requests"],
                        control=control)
    print(f"reference: {got['requests']} requests, {got['tokens']} served "
          f"tokens, {time.perf_counter() - t_ref:.1f} s", file=log)
    print(f"readings: {json.dumps(got)}", file=log)
    checks = {name: {"value": got.get(name), "limit": limit}
              for name, limit in corr["limits"].items()}
    checks["tick_errors"] = {"value": tick_errors, "limit": 0}
    checks["failed_requests"] = {"value": len(failed), "limit": 0}
    result["correct"] = bool(corr["limits"]) and all(
        c["value"] is not None and c["limit"] is not None
        and c["value"] <= c["limit"] for c in checks.values())
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_check"] = got
    result["_compiles_in_window"] = compiles
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    except ImportError as e:
        print(f"run.py: the repo's src/ is not beside benchmarks/ ({e})",
              file=sys.stderr)
        return 2
    for name, chk in res["checks"].items():
        print(f"check {name}: {chk['value']} (limit {chk['limit']})",
              file=sys.stderr)
    for k in ("_check", "_compiles_in_window"):
        res.pop(k)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
