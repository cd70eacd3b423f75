"""Training batches of seeded token ids, one fixed shape.

Ids follow a skewed unigram law (``floor(V * u**skew)``), the same for every
seed: a model can learn it in a few steps, so "the loss falls" is a check that
does not hang on memorising one batch. ``seed`` draws the ids.
"""

from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, params: dict, vocab_size: int, seed: int):
        self.seq_len = int(params["seq_len"])
        self.micro_batch_per_chip = int(params["micro_batch_per_chip"])
        self.skew = float(params.get("skew", 3.0))
        self.vocab_size = vocab_size
        self._rng = np.random.default_rng(np.random.SeedSequence(seed))

    def _ids(self, rows: int) -> np.ndarray:
        u = self._rng.random((rows, self.seq_len))
        return np.minimum((self.vocab_size * u ** self.skew).astype(np.int32),
                          self.vocab_size - 1)

    def batch(self, chips: int) -> np.ndarray:
        return self._ids(self.micro_batch_per_chip * chips)

    def sample_batch(self, chips: int, distinct: int) -> np.ndarray:
        """A global batch made of ``distinct`` sequences repeated to fill it:
        its mean loss is the mean loss of those few, which the reference can
        afford."""
        rows = self.micro_batch_per_chip * chips
        base = self._ids(distinct)
        return np.tile(base, (-(-rows // distinct), 1))[:rows]
