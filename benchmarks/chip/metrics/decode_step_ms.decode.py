"""Fused device step: mean device time of one execution of the decode
step (module ``jit_decode_step`` on the trace's ``XLA Modules`` line),
over the executions that lie wholly in the traced span, averaged over
the chips, in ms."""
from harness import steps


def read(w):
    s = steps.for_window(w)
    return None if s is None else s.step_ms("jit_decode_step")
