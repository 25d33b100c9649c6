"""Engine host loop: host time of the engine thread per tick, in ms: the
engine's engine.plan, engine.dispatch and engine.ingest spans that lie
wholly in the traced span (they time what the counters plan_ns,
dispatch_ns and ingest_ns count) over the engine's ticks in the span.
Readback waits and idle waits are not counted."""
from harness import steps


def read(w):
    s = steps.for_window(w)
    ticks = w.delta("ticks")
    if s is None or not ticks or not any(n in s.span_s
                                         for n in steps.ENGINE_HOST):
        return None
    return sum(s.span_s.get(n, 0.0) for n in steps.ENGINE_HOST) / ticks * 1e3
