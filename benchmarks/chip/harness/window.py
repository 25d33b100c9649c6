"""What a run saw in a span of time, and the end-to-end statistics over it.

The per-layer readers (``metrics/<name>.py``) take a ``Window``: the
client records, the engine's counters at the span's two ends, the trace
summary of the span (traced runs), and the sizes they divide by.  A
counter an engine lacks is left out of both ends, and the readers of it
read nothing: a parent program's traced runs use this harness too.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from harness import counts
from harness.serve import Record
from harness.tracefile import Summary

COUNTERS = ("ticks", "prefill_tokens", "prefill_ticks", "prefill_rows")


def counters(engine) -> Dict[str, int]:
    return {k: int(getattr(engine, k)) for k in COUNTERS
            if hasattr(engine, k)}


def p95(values) -> Optional[float]:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    records: List[Record]
    before: Dict[str, int]
    after: Dict[str, int]
    model: dict
    engine: dict
    device_kind: str
    # of the configuration's weights module: its count for ``model``
    matmul_weights_per_token: int
    trace: Optional[Summary] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def delta(self, name: str) -> int:
        return self.after[name] - self.before[name]

    @property
    def peaks(self) -> dict:
        return counts.peaks(self.device_kind)

    def tokens(self):
        """(record, index, time) of every token delivered in the span."""
        for r in self.records:
            for i, t in enumerate(r.times):
                if self.t0 <= t <= self.t1:
                    yield r, i, t

    def token_gaps_ms(self) -> List[float]:
        """Every gap between consecutive tokens of one request, both
        tokens inside the span."""
        out = []
        for r in self.records:
            ts = [t for t in r.times if self.t0 <= t <= self.t1]
            out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
        return out

    def decode_kv_lens(self) -> List[int]:
        """kv_len of the decode row that produced each token in the span:
        token i (i >= 1) of a request with prompt length L was sampled
        by the decode step at position L + i - 1, which reads L + i
        keys."""
        return [len(r.spec.prompt) + i for r, i, _ in self.tokens() if i]
