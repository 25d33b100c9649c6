"""Fused device step: share of the chip's bf16 peak that the useful
matmul work of the span reaches: (prompt tokens prefilled + tokens
delivered) x 2 x matmul weights per token / span / peak.  Pad rows,
embedding lookups and attention arithmetic are not counted."""
from harness import counts


def read(w):
    toks = w.delta("prefill_tokens") + sum(1 for _ in w.tokens())
    if not toks:
        return None
    flops = toks * counts.matmul_flops_per_token(w.model)
    return 100.0 * flops / w.seconds / w.peaks["bf16_flops"]
