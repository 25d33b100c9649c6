"""The one traffic generator: turns a mix file (``traffic/<name>.json``)
and a seed into the requests a run sends.

Every seed gets the same set of sizes, in another order: prompt and
output lengths are the quantiles ``(i + 0.5) / n`` of their
distribution, shuffled by the seed within blocks of ``BLOCK`` requests.
So two seeds do the same work, and only the order and the token ids
differ.  One request in every ``greedy_every`` (at a seeded place in each
block of that many) is greedy: those are the requests whose tokens the
correctness check can compare with the reference.  No two requests open
with the same token (while the vocabulary lasts), so nothing is shared:
the engine's prefix cache forks on a match of even one token against a
prompt that filled whole pages.

A mix file holds:

  loop        "closed": ``clients`` callers, each sends its next request
              when the previous one has finished
  prompt      length distribution: {"dist": "uniform", "lo", "hi"} or
              {"dist": "lognormal", "median", "sigma", "lo", "hi"}
  max_new     output length distribution, same forms; there are no stop
              tokens, so it is each request's output length
  sampling    {"temperature", "top_k", "top_p"} of the sampled requests
  greedy_every  one greedy request in this many
  steady_first_wave  each client's first request starts part way
              through its output: that many of its output tokens are
              moved into its prompt, so the measured window opens on a
              staggered batch and not on a ramp.  The ages are the
              quantiles of a uniform; ages and prompt lengths are paired
              with output lengths by rank through fixed permutations, so
              the first wave's (prompt, output left) pairs are the same
              set for every seed: a window then holds the same
              completions, and the same prefills, whatever the seed
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np

BLOCK = 64
_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class RequestSpec:
    index: int
    prompt: List[int]
    max_new: int
    greedy: bool


def _quantile(dist: dict, u: float) -> int:
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = dist["lo"], dist["hi"]
        return min(hi, lo + int(u * (hi - lo + 1)))
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(dist["hi"], max(dist["lo"], round(x))))
    raise ValueError(f"unknown length distribution {kind!r}")


def _strata(rng: np.random.Generator, n: int, block: int) -> np.ndarray:
    """n quantile levels: each block of ``block`` holds (i + 0.5) / block
    for i < block, shuffled."""
    out = []
    while len(out) < n:
        out.extend(((rng.permutation(block) + 0.5) / block).tolist())
    return np.asarray(out[:n])


def seed_words(seed: int) -> np.random.SeedSequence:
    """The seed is any whole number, larger than 32 bits too."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return np.random.SeedSequence(seed)


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.loop = mix["loop"]
        if self.loop != "closed":
            raise ValueError(f"loop must be 'closed', got {self.loop!r}")
        self.clients = int(mix.get("clients", 0))
        self.sampling = mix["sampling"]
        ss = seed_words(seed)
        self._rng = np.random.default_rng(ss.spawn(1)[0])
        self._first = self._rng.permutation(vocab)
        self._made = 0

    def requests(self, n: int, block: int = BLOCK) -> List[RequestSpec]:
        """The next n requests of this seed's stream (deterministic)."""
        rng = self._rng
        p_u = _strata(rng, n, block)
        m_u = _strata(rng, n, block)
        every = int(self.mix.get("greedy_every", 0))
        greedy = np.zeros(n, bool)
        if every:
            for b in range(0, n, every):
                greedy[b + int(rng.integers(0, min(every, n - b)))] = True
        out = []
        for i in range(n):
            plen = _quantile(self.mix["prompt"], p_u[i])
            prompt = rng.integers(0, self.vocab, plen)
            prompt[0] = self._first[(self._made + i) % self.vocab]
            out.append(RequestSpec(
                index=self._made + i,
                prompt=prompt.tolist(),
                max_new=_quantile(self.mix["max_new"], m_u[i]),
                greedy=bool(greedy[i])))
        self._made += n
        return out

    def first_wave(self) -> List[RequestSpec]:
        """Each client's first request, staggered as the mix says; its
        lengths are the quantiles of one block of ``clients``."""
        n = self.clients
        wave = self.requests(n, block=n)
        if not self.mix.get("steady_first_wave"):
            return wave
        ages = (np.arange(n) + 0.5) / n
        fixed = np.random.default_rng(0)
        rank_prompt, rank_age = fixed.permutation(n), fixed.permutation(n)
        prompt_lens = sorted(len(r.prompt) for r in wave)
        by_len = np.argsort([r.max_new for r in wave], kind="stable")
        for k, i in enumerate(by_len):
            r = wave[i]
            moved = int(ages[rank_age[k]] * r.max_new)
            total = prompt_lens[rank_prompt[k]] + moved
            r.prompt = (r.prompt + self._rng.integers(
                0, self.vocab, max(0, total - len(r.prompt))).tolist())[:total]
            r.max_new -= moved
        return wave
