"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the greedy requests the window served, drawn from the seed and always
holding the one with the longest context, goes through the plain
reference: prompt and served tokens, once.  At each served token the
reference gives the gap by which that token's logit lies below its best
logit there; ``readings`` reduces the gaps to the widest, the mean and the
share off the argmax, and a configuration names the ones it compares.
Greedy tokens are the program's argmax, so a sound run reads only
rounding there; a dropped write or an altered token reads the distance to
another token.
"""

from __future__ import annotations

from typing import List

import numpy as np

from harness.serve import Record


def sample(records: List[Record], seed: int, n: int) -> List[Record]:
    pool = [r for r in records if r.spec.greedy and r.tokens
            and r.error is None]
    if not pool:
        return []
    pool.sort(key=lambda r: (-(len(r.spec.prompt) + len(r.tokens)),
                             r.spec.index))
    rest = pool[1:]
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(2)[1])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [pool[0]] + [rest[i] for i in sorted(pick)]


def readings(gaps: np.ndarray) -> dict:
    """The numbers a cell may compare, over every served token of the
    sample: the widest gap, the mean gap, and the share of tokens that are
    not the reference's argmax."""
    return {"logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "off_argmax_share": float((gaps > 0).mean())}


def compare(ref, records: List[Record], seed: int, n: int, *,
            control: bool = False) -> dict:
    chosen = sample(records, seed, n)
    seqs = [(r.spec.prompt, r.tokens) for r in chosen]
    out = {"requests": len(chosen),
           "tokens": int(sum(len(r.tokens) for r in chosen))}
    if not chosen:
        return out
    got = ref.readings(seqs, control=control)
    out.update(readings(np.concatenate([g["gap"] for g in got])))
    if control:
        ctrl = readings(np.concatenate([g["control_gap"] for g in got]))
        out.update({f"control_{k}": v for k, v in ctrl.items()})
    return out
