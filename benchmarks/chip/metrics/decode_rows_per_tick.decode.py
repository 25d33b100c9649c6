"""Scheduler: live decode rows per decode tick: tokens made by decode
steps in the span (every token but a request's first) over the engine's
decode ticks in it."""


def read(w):
    ticks = w.delta("ticks")
    if not ticks:
        return None
    return sum(1 for _, i, _ in w.tokens() if i) / ticks
