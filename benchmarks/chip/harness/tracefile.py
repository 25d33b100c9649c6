"""Reduction of a profiler trace (``.xplane.pb``) to device time.

Read with ``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; their op line (``XLA Ops``) holds one event per
operation run on the chip.  The traced window is the host span the
harness opens around it (``WINDOW``), on the same clock.

- busy: the union of op intervals inside the window, per chip, averaged
  over the chips;
- per-op totals: summed durations by op name, clipped to the window;
- idle gaps: the holes in chip 0's union, longest first, each named by the
  host span that overlaps it most (the harness's own spans around client
  submits and sinks, and JAX's runtime events on the host threads).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OP_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the chips
    chips: int
    op_s: Dict[str, float]  # summed over chips / chips
    gaps: List[Tuple[str, float]]  # (what the host did, seconds), longest

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, names) -> float:
        """Device seconds of ops whose name contains one of ``names``."""
        return sum(s for op, s in self.op_s.items()
                   if any(n in op for n in names))


_OP = re.compile(r"^(%?[\w.\-]+) = .*?\b([a-z][\w\-]*)\(")


def op_name(text: str) -> str:
    """``%fusion.17 = f32[...] fusion(...)`` -> ``%fusion.17 fusion``: the
    trace's op events carry the whole HLO instruction."""
    m = _OP.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:120]


def find(log_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    return hits[-1] if hits else None


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _window(host_planes) -> Tuple[float, float]:
    for plane in host_planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    return float(ev.start_ns), float(ev.end_ns)
    raise ValueError(f"no {WINDOW!r} span in the trace")


def reduce(path: str, top_gaps: int = 10) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    devs = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)
                   and p.name[len(DEVICE_PREFIX):].isdigit()),
                  key=lambda p: int(p.name[len(DEVICE_PREFIX):]))
    if not devs:
        raise ValueError("no TPU device plane in the trace")
    w0, w1 = _window(host)
    op_ns: Dict[str, float] = {}
    busy_ns = 0.0
    first_union = None
    for plane in devs:
        iv = []
        for line in plane.lines:
            if line.name != OP_LINE:
                continue
            for ev in line.events:
                a, b = max(float(ev.start_ns), w0), min(float(ev.end_ns), w1)
                if b <= a:
                    continue
                iv.append((a, b))
                name = op_name(ev.name)
                op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        u = _union(iv)
        busy_ns += sum(b - a for a, b in u)
        if first_union is None:
            first_union = u
    chips = len(devs)
    holes = []
    prev = w0
    for a, b in first_union or []:
        if a > prev:
            holes.append((prev, a))
        prev = max(prev, b)
    if prev < w1:
        holes.append((prev, w1))
    holes.sort(key=lambda h: h[0] - h[1])
    holes = holes[:top_gaps]
    spans = [(float(ev.start_ns), float(ev.end_ns), ev.name)
             for p in host for line in p.lines for ev in line.events
             if ev.name != WINDOW and ev.end_ns > w0 and ev.start_ns < w1]
    gaps = []
    for a, b in holes:
        best, name = 0.0, "nothing on the host"
        for s, e, n in spans:
            ov = min(b, e) - max(a, s)
            if ov > best and (e - s) < 10 * (w1 - w0):
                best, name = ov, n
        gaps.append((name, (b - a) * 1e-9))
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns / chips * 1e-9,
                   chips=chips,
                   op_s={k: v / chips * 1e-9 for k, v in op_ns.items()},
                   gaps=gaps)


def dump(path: str, out, max_events: int = 8) -> None:
    """Print the planes, lines and first events of a trace (for looking at
    one by hand)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}", file=out)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events", file=out)
            for ev in evs[:max_events]:
                stats = [(k, str(v)[:80]) for k, v in ev.stats]
                print(f"    {ev.name!r} {ev.start_ns} {ev.duration_ns} "
                      f"{stats}", file=out)
