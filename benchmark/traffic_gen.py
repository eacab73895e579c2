"""The one traffic generator: a mix file's parameters in, requests out.

A serve mix gives the shape of the load, nothing about the model: the
distributions of prompt and output lengths, how much of each prompt is shared
(``shared_prefix``, optional), and how requests arrive: ``"loop": "closed"``
with ``clients`` that each send their next request when the last is answered,
or ``"loop": "open"`` with arrivals at ``rate_per_s`` with exponential gaps,
served by a pool of ``clients`` connections. No cell uses the open loop or the
shared prefix yet; they are here because a later PR may add a cell only as
data (PERF.md section 7, rows 1 and 2).

Every seed gets the same sizes (and gaps), in another order, as the
benchmark's contract asks: a cycle of ``cycle`` requests holds exactly the
``(i + 1/2) / cycle`` quantiles of each distribution, so no size is drawn at
random and the file needs no seed of its own. The run's seed permutes each
cycle afresh, prompts and outputs apart, and draws the token ids.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-point quantiles of ``{"dist", ...}`` as whole numbers
    clipped to ``[min, max]``: ``lognormal`` (``median`` and either ``sigma`` or
    the ``mean``, from which sigma follows: mean = median x exp(sigma^2 / 2))
    or, unclipped and not rounded, ``exponential`` (``mean``)."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        sigma = spec.get("sigma")
        if sigma is None:
            sigma = math.sqrt(2.0 * math.log(spec["mean"] / spec["median"]))
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(sigma * z)
    elif spec["dist"] == "exponential":
        return -spec["mean"] * np.log1p(-u)
    else:
        raise ValueError(f"unknown dist {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Plan:
    """Request ``i`` of a mix under a seed: ``sizes(i)`` gives its prompt
    length, the tokens it asks for and (open loop) the second it is due;
    ``prompt(i)`` its ids. Cycle ``c`` is requests ``c * cycle`` onward."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.vocab, self.seed = int(vocab), int(seed)
        self.cycle = int(mix["cycle"])
        self.prompt_q = quantiles(mix["prompt_len"], self.cycle)
        self.new_q = quantiles(mix["max_new_tokens"], self.cycle)
        self.open = mix["loop"] == "open"
        self.gap_q = (quantiles({"dist": "exponential", "mean": 1.0 / mix["rate_per_s"]},
                                self.cycle) if self.open else np.zeros(self.cycle))
        share = mix.get("shared_prefix") or {"groups": 0, "tokens": 0}
        self.groups = int(share["groups"])
        self.prefixes = np.random.default_rng([self.seed, 0x70726566]).integers(
            0, self.vocab, (max(self.groups, 1), int(share["tokens"])), dtype=np.int32)
        self._cycles: dict = {}

    def _cycle(self, c: int):
        if c not in self._cycles:
            rng = np.random.default_rng([self.seed, 0x6F726465, c])
            gaps = self.gap_q[rng.permutation(self.cycle)]
            self._cycles[c] = (self.prompt_q[rng.permutation(self.cycle)],
                               self.new_q[rng.permutation(self.cycle)],
                               c * float(self.gap_q.sum()) + np.cumsum(gaps))
        return self._cycles[c]

    def sizes(self, i: int) -> tuple[int, int, float]:
        c, j = divmod(int(i), self.cycle)
        prompt_len, new_tokens, due = self._cycle(c)
        return int(prompt_len[j]), int(new_tokens[j]), float(due[j])

    def prompt(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 0x746F6B73, int(i)])
        own = rng.integers(0, self.vocab, self.sizes(i)[0], dtype=np.int32)
        if self.groups:
            head = self.prefixes[i % self.groups][: len(own) - 1]
            own[: len(head)] = head
        return own
