"""Lookup-id generators, seeded, host-side numpy.

Every generator takes a ``numpy.random.Generator`` made from the run's
``--seed`` (:func:`rng`), so the same seed gives the same inputs.

Zipf: a true Zipf law over ranks, ``P(rank k) = k**-s / H(N, s)`` for
``k = 1..N``, drawn by inverting the exact CDF, and each table's ranks
mapped to rows through a seeded permutation, so that the hot rows lie
scattered over the table as hashed ids do (not side by side at the low
ids, which would flatter any kernel that works per group of rows).
"""

from __future__ import annotations

import functools

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one run: any whole number as
    the seed (numpy takes arbitrarily large ones)."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


@functools.lru_cache(maxsize=4)
def zipf_cdf(n: int, exponent: float) -> np.ndarray:
    """Exact CDF of the Zipf law over ranks 1..n, float64, last entry 1
    (one array per law, shared by the tables that draw from it)."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(exponent)
    c = np.cumsum(w)
    return c / c[-1]


class TableIds:
    """Row ids for one table of ``rows`` rows: uniform, or Zipf over
    ranks with a seeded rank-to-row permutation (a static hot set)."""

    def __init__(self, rows: int, dist: str, g: np.random.Generator,
                 exponent: float = 0.0):
        if dist not in ("uniform", "zipf"):
            raise ValueError(f"unknown id distribution {dist!r}")
        self.rows, self.dist = int(rows), dist
        if dist == "zipf":
            self.cdf = zipf_cdf(self.rows, float(exponent))
            self.perm = g.permutation(self.rows).astype(np.int32)

    def draw(self, g: np.random.Generator, shape) -> np.ndarray:
        if self.dist == "uniform":
            return g.integers(0, self.rows, shape, dtype=np.int32)
        ranks = np.searchsorted(self.cdf, g.random(shape), side="right")
        return self.perm[np.minimum(ranks, self.rows - 1)]
