"""Scheduler: share of the prefill step's rows x positions that are
padding, from the engine's counters: 1 - prefill_tokens / (prefill_rows x
prefill_slice).  A prefill step computes ``prefill_rows`` rows (the
prefilling slots, packed and padded to a bucket) of ``prefill_slice``
positions each, not every slot."""


def read(w):
    if "prefill_rows" not in w.after:
        return None
    rows = w.delta("prefill_rows")
    if not rows:
        return None
    positions = rows * w.engine["prefill_slice"]
    return 1.0 - w.delta("prefill_tokens") / positions
