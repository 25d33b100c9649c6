"""Device time by jitted step, and the engine thread's spans, from a
profiler trace (``.xplane.pb``): what ``harness/tracefile.py`` does not
split.

Each jitted step of the engine is its own XLA module, named
``jit_<function>`` (``jit_prefill_step``, ``jit_decode_step``, ...).  A
device plane's ``XLA Modules`` line holds one event per execution of a
module, named ``<module>(<fingerprint>)``; its ``XLA Ops`` line holds the
ops, which run inside one execution.

- per module: device seconds and executions, of the executions that lie
  wholly in the window, averaged over the chips;
- per (module, op): op seconds, clipped to the window, averaged over the
  chips; an op is attributed to the module execution that contains its
  start on the same plane (``NO_MODULE`` where none does);
- per host span name: seconds and count of the spans that lie wholly in
  the window (the engine thread's ``engine.*`` spans among them).

The per-layer readers take a ``Window`` whose ``trace`` is tracefile's
summary; ``for_window`` finds the trace file it was read from (the
newest under ``_runs/trace-*`` whose window span has the same length).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

from harness import tracefile

RUNS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "_runs")
MODULE_LINE = "XLA Modules"
NO_MODULE = "(no module)"
ENGINE_HOST = ("engine.plan", "engine.dispatch", "engine.ingest")

_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Steps:
    window_s: float
    chips: int
    module_s: Dict[str, float]  # device seconds, summed / chips
    module_n: Dict[str, float]  # executions, counted / chips
    op_s: Dict[Tuple[str, str], float]  # (module, op) -> seconds / chips
    span_s: Dict[str, float]  # host span name -> seconds
    span_n: Dict[str, int]  # host span name -> count

    def step_ms(self, module: str) -> Optional[float]:
        """Mean device time of one execution of ``module``, in ms."""
        n = self.module_n.get(module)
        return self.module_s[module] / n * 1e3 if n else None


def module_name(text: str) -> str:
    """``jit_decode_step(1069...)`` -> ``jit_decode_step``."""
    return _FINGERPRINT.sub("", text)


def reduce(path: str) -> Steps:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    devs = [p for p in planes if p.name.startswith(tracefile.DEVICE_PREFIX)
            and p.name[len(tracefile.DEVICE_PREFIX):].isdigit()]
    if not devs:
        raise ValueError("no TPU device plane in the trace")
    w0, w1 = tracefile._window(host)
    module_ns: Dict[str, float] = {}
    module_n: Dict[str, int] = {}
    op_ns: Dict[Tuple[str, str], float] = {}
    for plane in devs:
        lines = {line.name: list(line.events) for line in plane.lines}
        runs = sorted((float(ev.start_ns), float(ev.end_ns),
                       module_name(ev.name))
                      for ev in lines.get(MODULE_LINE, ()))
        for a, b, name in runs:
            if w0 <= a and b <= w1:
                module_ns[name] = module_ns.get(name, 0.0) + (b - a)
                module_n[name] = module_n.get(name, 0) + 1
        starts = [a for a, _, _ in runs]
        for ev in lines.get(tracefile.OP_LINE, ()):
            s, e = float(ev.start_ns), float(ev.end_ns)
            a, b = max(s, w0), min(e, w1)
            if b <= a:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = runs[i][2] if i >= 0 and s < runs[i][1] else NO_MODULE
            key = (mod, tracefile.op_name(ev.name))
            op_ns[key] = op_ns.get(key, 0.0) + (b - a)
    span_ns: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for p in host:
        for line in p.lines:
            for ev in line.events:
                if ev.name != tracefile.WINDOW and (
                        w0 <= ev.start_ns and ev.end_ns <= w1):
                    span_ns[ev.name] = span_ns.get(ev.name, 0.0) + float(
                        ev.end_ns - ev.start_ns)
                    span_n[ev.name] = span_n.get(ev.name, 0) + 1
    chips = len(devs)
    return Steps(window_s=(w1 - w0) * 1e-9, chips=chips,
                 module_s={k: v / chips * 1e-9 for k, v in module_ns.items()},
                 module_n={k: v / chips for k, v in module_n.items()},
                 op_s={k: v / chips * 1e-9 for k, v in op_ns.items()},
                 span_s={k: v * 1e-9 for k, v in span_ns.items()},
                 span_n=span_n)


def top_ops(steps: Steps, n: int = 10) -> List[List]:
    """The ``n`` (module, op) pairs with the most device time:
    ``[module, op, seconds]``, longest first."""
    ops = sorted(steps.op_s.items(), key=lambda kv: -kv[1])[:n]
    return [[m, op, s] for (m, op), s in ops]


# (path, mtime) -> summary: the readers of one run parse its trace once
_CACHE: Dict[Tuple[str, float], Steps] = {}


def for_window(w) -> Optional[Steps]:
    """The step summary of the trace that ``w.trace`` was reduced from,
    or None (an untraced run, or no such trace)."""
    if w.trace is None:
        return None
    paths = glob.glob(os.path.join(RUNS_DIR, "trace-*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        key = (path, os.path.getmtime(path))
        if key not in _CACHE:
            try:
                _CACHE[key] = reduce(path)
            except ValueError:
                continue
        if _CACHE[key].window_s == w.trace.window_s:
            return _CACHE[key]
    return None


def main(argv=None) -> int:
    """Print a trace's module and (module, op) breakdown as JSON:
    ``PYTHONPATH=benchmarks/chip python3 -m harness.steps <xplane.pb>``."""
    import json

    path = (argv or sys.argv[1:])[0]
    s = reduce(path)
    print(json.dumps({
        "window_s": s.window_s,
        "modules": {m: {"s": s.module_s[m], "n": s.module_n[m],
                        "ms": s.step_ms(m)} for m in sorted(s.module_s)},
        "device_ops_by_module": top_ops(s, 25),
        "spans": {k: {"s": s.span_s[k], "n": s.span_n[k]}
                  for k in sorted(s.span_s)},
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
