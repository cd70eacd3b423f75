"""Closed loop over a fixed grid of (prompt length, output length) pairs.

The traffic file fixes the grid: ``prompt_lens`` x ``output_lens``. Requests
cycle through it in an order permuted inside each cycle; ``seed`` draws the
token ids. Two seeds give the same multiset of work.

``order`` says what permutes: ``"by_seed"`` (the default) takes ``seed``, so
two seeds give the same work in another order; ``"fixed"`` takes the traffic
file's ``order_seed``, so every seed gives the same order and only the token
ids (and the weights) differ. A closed loop that is driven step by step is
deterministic in its schedule, so under ``"fixed"`` every run admits the same
requests in the same scheduler steps, and runs differ by timing alone. That
matters where the order decides the work: which prompts share an admission
cycle decides whether the padded admission-batch program runs (PERF.md,
PR 23: three seeds spread 4.4% on ``out_tok_s`` under ``"by_seed"``).

The first fill of the slots is staggered: each of its requests has its output
length cut at a seeded uniform fraction of ``min(length, stagger_cap)``, so
the slots do not finish in lockstep and all have turned over after at most
``stagger_cap`` decode steps.
"""

from __future__ import annotations

import itertools
from typing import Iterator, List, Tuple

import numpy as np

Item = Tuple[np.ndarray, int]     # prompt token ids, tokens to generate


class Traffic:
    def __init__(self, params: dict, vocab_size: int, seed: int):
        self.grid = list(itertools.product(params["prompt_lens"],
                                           params["output_lens"]))
        self.stagger_cap = int(params.get("stagger_cap", 0)) or max(
            o for _, o in self.grid)
        self.vocab_size = vocab_size
        self._rng = np.random.default_rng(np.random.SeedSequence(seed))
        order = params.get("order", "by_seed")
        if order == "fixed":
            self._order_rng = np.random.default_rng(
                np.random.SeedSequence(int(params["order_seed"])))
        elif order == "by_seed":
            self._order_rng = np.random.default_rng(
                np.random.SeedSequence([seed, 1]))
        else:
            raise ValueError(f"order must be by_seed or fixed, got {order!r}")
        self._stream = self._cycle()

    def prompt_lengths(self) -> List[int]:
        return sorted({p for p, _ in self.grid})

    def _cycle(self) -> Iterator[Tuple[int, int]]:
        while True:
            for i in self._order_rng.permutation(len(self.grid)):
                yield self.grid[i]

    def _prompt(self, length: int) -> np.ndarray:
        return self._rng.integers(0, self.vocab_size, size=length,
                                  dtype=np.int32)

    def next(self) -> Item:
        p, o = next(self._stream)
        return self._prompt(p), o

    def first_fill(self, n: int) -> List[Item]:
        out = []
        for _ in range(n):
            p, o = next(self._stream)
            cut = int(np.ceil(self._order_rng.uniform()
                              * min(o, self.stagger_cap)))
            out.append((self._prompt(p), max(1, cut)))
        return out

    def lengths(self, n: int) -> List[Tuple[int, int]]:
        """The (prompt, output) lengths of the next ``n`` requests, without
        drawing token ids (for the tests)."""
        return [next(self._stream) for _ in range(n)]
