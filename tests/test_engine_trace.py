"""The engine's names on a profiler trace: each jitted step is a module
named after its function, and the engine thread's ticks lay leaf spans
(``engine.plan`` / ``dispatch`` / ``readback`` / ``ingest``, and the
runner's ``engine.drain`` / ``engine.idle``) on the trace's clock, with
integer-ns counters beside them that count with the profiler off too."""

import glob
import os
import re
import subprocess
import sys
import threading

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import smoke_config
from repro.models import get_model_def
from repro.models.module import init_params
from repro.serving import Request, SamplingParams, ServeEngine
from repro.serving.gateway import EngineRunner

ROOT = os.path.join(os.path.dirname(__file__), "..")
COUNTERS = ("plan_ns", "dispatch_ns", "ingest_ns")


def _engine():
    cfg = smoke_config("codeqwen1.5-7b").replace(attn_backend="dense")
    md = get_model_def(cfg)
    params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
    return ServeEngine(md, cfg, params, max_batch=2, max_len=64, page_size=8)


def _req(i):
    return Request(prompt=[3 + i, 5, 8, 13, 21],
                   sampling=SamplingParams(max_new=4))


def _host_spans(log_dir):
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(ev.name, dict(ev.stats))
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name.startswith("engine.")]


def test_engine_spans_and_counters(tmp_path):
    eng = _engine()
    for _ in eng.stream(_req(0)):  # compiles outside the trace
        pass
    # the counters count with the profiler off
    assert all(isinstance(getattr(eng, c), int) and getattr(eng, c) > 0
               for c in COUNTERS)
    before = {c: getattr(eng, c) for c in COUNTERS}

    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in (1, 2):
            eng.submit(_req(i))
        dispatching = 0
        while eng.has_work or eng.has_pending:
            dispatching += eng.has_work
            eng.poll()
    finally:
        jax.profiler.stop_trace()
    assert all(getattr(eng, c) > before[c] for c in COUNTERS)

    spans = _host_spans(str(tmp_path))
    names = {n for n, _ in spans}
    assert {"engine.plan", "engine.dispatch", "engine.readback",
            "engine.ingest"} <= names
    dispatches = [args for n, args in spans if n == "engine.dispatch"]
    assert len(dispatches) == dispatching  # one per dispatching poll
    # (a poll whose plan enqueues nothing carries no steps)
    steps = {s for a in dispatches for s in a.get("steps", "").split("+")}
    assert steps - {""} == {"prefill_step", "decode_step"}
    # a tick that carries a prefill names its batch's rows: both requests
    # join in one tick, two prefilling slots in a 2-row batch
    rows = [a["rows"] for a in dispatches
            if "prefill_step" in a.get("steps", "")]
    assert rows == [2]
    assert all("rows" not in a for a in dispatches
               if "prefill_step" not in a.get("steps", ""))
    # every span carries the tick it belongs to
    assert all(isinstance(args["tick"], int) for _, args in spans)


def test_runner_spans(tmp_path):
    eng = _engine()
    for _ in eng.stream(_req(0)):
        pass
    runner = EngineRunner(eng, idle_wait_s=0.001)
    done = threading.Event()
    jax.profiler.start_trace(str(tmp_path))
    try:
        runner.start()
        runner.submit(_req(1), lambda out: out.finished and done.set())
        assert done.wait(120)
    finally:
        assert runner.stop(60)
        jax.profiler.stop_trace()
    names = {n for n, _ in _host_spans(str(tmp_path))}
    assert {"engine.drain", "engine.idle", "engine.dispatch"} <= names


# Each step the engine jits lowers to a module named after its function
# (the device trace's "XLA Modules" line shows these names); at tp > 1 the
# shard_map wrapper keeps the name.  A subprocess: the device count and
# the IR dump are fixed before jax starts.
_LOWER = """
import jax
from repro.configs import smoke_config
from repro.models import get_model_def
from repro.models.module import init_params
from repro.serving import Request, SamplingParams, ServeEngine

cfg = smoke_config("codeqwen1.5-7b").replace(attn_backend="dense")
md = get_model_def(cfg)
params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
prompt = list(range(3, 23))  # two full pages and a shared boundary page
for spec_k in (0, 2):
    eng = ServeEngine(md, cfg, params, max_batch=2, max_len=64, page_size=8,
                      tp={tp}, spec_k=spec_k)
    for p in (prompt, prompt + [5]):  # the second forks the first's pages
        for _ in eng.stream(Request(prompt=p,
                                    sampling=SamplingParams(max_new=3))):
            pass
"""


@pytest.mark.parametrize("tp", [1, 2])
def test_each_step_lowers_to_a_named_module(tmp_path, tp):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", JAX_DUMP_IR_TO=str(tmp_path),
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={tp}")
    r = subprocess.run([sys.executable, "-c", _LOWER.format(tp=tp)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    modules = {m.group(1) for f in os.listdir(tmp_path)
               for m in [re.match(r"jax_ir\d+_(jit_\w+?)_compile", f)] if m}
    assert {"jit_prefill_step", "jit_decode_step", "jit_fork_page",
            "jit_spec_prefill_step", "jit_spec_step"} <= modules, modules
    assert "jit_fn" not in modules
