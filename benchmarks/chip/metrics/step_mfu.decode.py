"""Fused device step: share of the chip's bf16 peak that the useful
matmul work of the span reaches: (prompt tokens prefilled + tokens
delivered) x 2 x matmul weights per token / span / peak.  The weights
per token are the configuration's weights module's count.  Pad rows,
embedding lookups and attention arithmetic are not counted."""


def read(w):
    toks = w.delta("prefill_tokens") + sum(1 for _ in w.tokens())
    if not toks:
        return None
    flops = toks * 2 * w.matmul_weights_per_token
    return 100.0 * flops / w.seconds / w.peaks["bf16_flops"]
