"""The one generator of training traffic: token rows drawn from the
seed as a traffic mix's data file says.

Batch ``i`` of a run depends only on (seed, i), so the reference can
draw the batches that the program trained on without taking them from
the program.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def batch_tokens(traffic: Dict, vocab: int, seed: int, i: int) -> np.ndarray:
    """(rows, seq) int32 token ids of batch ``i``."""
    if traffic["tokens"] != "uniform":
        raise ValueError(f"unknown token distribution {traffic['tokens']!r}")
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, vocab, size=(traffic["rows"], traffic["seq"]),
                        dtype=np.int32)


class TokenSource:
    """``next_batch()`` source for the program's ``data.pipeline``
    loader: batch 0, 1, 2, ... of (seed), shaped by ``shape_fn``."""

    def __init__(self, traffic: Dict, vocab: int, seed: int, shape_fn=None):
        self.traffic, self.vocab, self.seed = traffic, vocab, seed
        self.shape_fn = shape_fn or (lambda t: t)
        self.i = 0

    def next_batch(self) -> Dict[str, np.ndarray]:
        toks = batch_tokens(self.traffic, self.vocab, self.seed, self.i)
        self.i += 1
        return {"tokens": self.shape_fn(toks)}
