"""Scheduler: share of the prefill step's rows that are padding, from the
engine's counters: 1 - prefill_tokens / (prefill_ticks * max_batch *
prefill_slice).  The prefill step computes every slot's chunk whether it
prefills or not."""


def read(w):
    ticks = w.delta("prefill_ticks")
    if not ticks:
        return None
    rows = ticks * w.engine["max_batch"] * w.engine["prefill_slice"]
    return 1.0 - w.delta("prefill_tokens") / rows
