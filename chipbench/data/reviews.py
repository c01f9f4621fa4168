"""Review table for the UC4 review query, vectorised.

A copy of the distribution of ``repro.data.text.make_reviews``: topic food
or service with probability 1/2; length ``clip(lognormal(3, 0.9), 8, 512)``
truncated to an integer; each token drawn from the topic's 50 words
(food 10..59, service 60..109), replaced with probability 0.3 by a generic
word in 110..255; ratings uniform over 1..5. The one change is that the
one-star share of each query is exact (see ``ratings``), so that every seed
gives the model the same number of rows.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

FOOD_WORDS = np.arange(10, 60)
SERVICE_WORDS = np.arange(60, 110)
VOCAB = 256
MAX_LEN = 512


@dataclass
class ReviewPool:
    """Reviews at their real length: ``tokens[offsets[i]:offsets[i+1]]``."""

    tokens: np.ndarray    # flat uint8
    offsets: np.ndarray   # (n + 1,) int64
    max_len: int = MAX_LEN

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def padded(self, lo: int, hi: int) -> np.ndarray:
        """Reviews ``lo..hi-1`` as (hi - lo, max_len) int32, zero-padded."""
        off = self.offsets[lo:hi + 1]
        lens = np.diff(off)
        out = np.zeros((hi - lo, self.max_len), np.int32)
        rows = np.repeat(np.arange(hi - lo), lens)
        cols = np.arange(off[-1] - off[0]) - np.repeat(off[:-1] - off[0], lens)
        out[rows, cols] = self.tokens[off[0]:off[-1]]
        return out

    def row(self, i: int) -> np.ndarray:
        return self.tokens[self.offsets[i]:self.offsets[i + 1]].astype(np.int32)


def make_pool(n: int, rng: np.random.Generator, max_len: int = MAX_LEN) -> ReviewPool:
    lengths = np.clip(rng.lognormal(3.0, 0.9, n), 8, max_len).astype(np.int64)
    food = rng.random(n) < 0.5
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    total = int(offsets[-1])
    base = np.repeat(np.where(food, FOOD_WORDS[0], SERVICE_WORDS[0])
                     .astype(np.uint8), lengths)
    toks = base + rng.integers(0, 50, total, dtype=np.uint8)
    generic = rng.integers(SERVICE_WORDS[-1] + 1, VOCAB, total, dtype=np.uint8)
    mask = rng.integers(0, 10, total, dtype=np.uint8) < 3
    np.copyto(toks, generic, where=mask)
    return ReviewPool(toks, offsets, max_len)


def ratings(size: int, one_star: int, rng: np.random.Generator) -> np.ndarray:
    """``size`` ratings with exactly ``one_star`` ones at random places and
    the rest uniform over 2..5."""
    r = rng.integers(2, 6, size).astype(np.int32)
    r[rng.choice(size, one_star, replace=False)] = 1
    return r
