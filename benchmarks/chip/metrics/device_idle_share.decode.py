"""Device: share of the traced span in which no operation ran on the
chip (1 - union of op intervals / span)."""


def read(w):
    return None if w.trace is None else w.trace.idle_share
