"""Seeded generators for the traffic mixes.

:class:`MarkovSource` makes training sequences from a sparse bigram
chain with Zipf-weighted transitions (the idea of the program's
``data.synthetic.MarkovLM``, generated here so that the reference sees
the same rows without asking the program).  Sequence ``i`` depends only
on ``(seed, i)``, so the program's loader may batch and resume it
freely, and every seed gives the same amount of work.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def seeded_rng(seed: int, *stream: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    return np.random.default_rng([int(seed), *stream])


class MarkovSource:
    """``sample(start, batch, seq_len)`` → ``{"tokens", "labels"}``
    int32 ``(batch, seq_len)``: sequences ``[start, start + batch)`` of
    an endless stream, the interface ``PhaseDataLoader`` reads."""

    def __init__(self, seed: int, vocab: int, branching: int = 16,
                 zipf_a: float = 1.2):
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.branching = int(branching)
        rng = seeded_rng(self.seed, 1)
        # each token moves to one of `branching` successors, with Zipf
        # weights in a per-token order
        self.table = rng.integers(0, vocab, (vocab, branching),
                                  dtype=np.int64)
        w = np.arange(1, branching + 1, dtype=np.float64) ** (-zipf_a)
        probs = rng.permuted(np.broadcast_to(w, (vocab, branching)),
                             axis=1)
        self.cdf = np.cumsum(probs / probs.sum(1, keepdims=True), axis=1)

    def sample(self, start: int, batch: int, seq_len: int
               ) -> Dict[str, np.ndarray]:
        u = np.stack([seeded_rng(self.seed, 2, start + i)
                      .random(seq_len + 1) for i in range(batch)])
        toks = np.empty((batch, seq_len + 1), np.int32)
        state = (u[:, 0] * self.vocab).astype(np.int64)
        toks[:, 0] = state
        for t in range(1, seq_len + 1):
            j = (self.cdf[state] < u[:, t:t + 1]).sum(axis=1)
            state = self.table[state, np.minimum(j, self.branching - 1)]
            toks[:, t] = state
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

