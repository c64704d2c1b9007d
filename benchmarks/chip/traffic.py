"""The one traffic generator: open-loop arrivals and query ids from a mix.

A mix file fixes the *multiset* of what is sent: the inter-arrival gaps
are the exponential distribution's quantiles at the cell's rate, and the
popularity ranks are one draw from the mix's ``base_seed``.  A run's
seed only permutes them: the order of the gaps, the order of the ranks,
and which class holds which rank.  So every seed sends the same amount
of work with the same skew, and seeds differ the way users do.

Zipf ranks follow the repository's cache benchmark: P(rank i) is
proportional to (i + 1) ** -s, and ranks map to classes through a
seeded permutation, so the hot head is not index-local.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


def zipf_ranks(rng: np.random.Generator, n: int, size: int,
               s: float) -> np.ndarray:
    """``size`` ranks in [0, n) with P(rank i) proportional to (i+1)^-s."""
    p = 1.0 / np.arange(1, n + 1) ** s
    p /= p.sum()
    return rng.choice(n, size=size, p=p)


def ranks(ids: Dict[str, Any], n_items: int, size: int,
          seed: int) -> np.ndarray:
    """One draw of popularity ranks for the mix's ``ids`` law."""
    rng = np.random.default_rng(seed)
    law = ids["law"]
    if law == "zipf":
        return zipf_ranks(rng, n_items, size, float(ids["s"]))
    if law == "uniform":
        return rng.integers(0, n_items, size)
    raise ValueError(f"unknown id law {law!r}")


def arrival_offsets(rate: float, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate*seconds)``
    requests: exponential gaps at their quantiles, in seeded order."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = rng.permutation(gaps)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def schedule(mix: Dict[str, Any], n_items: int, rate: float,
             seconds: float, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(due offsets, table rows) of one open-loop window."""
    rng = np.random.default_rng(seed)
    offsets = arrival_offsets(rate, seconds, rng)
    base = ranks(mix["ids"], n_items, len(offsets), int(mix["base_seed"]))
    perm = item_permutation(n_items, seed)
    return offsets, perm[rng.permutation(base)]


def warm_rows(mix: Dict[str, Any], n_items: int, seed: int) -> np.ndarray:
    """Rows that warm the result cache in set-up: the same law and the
    same class permutation as the window, another draw."""
    n = int(mix.get("warm_cache_requests", 0))
    if n == 0:
        return np.zeros(0, np.int64)
    base = ranks(mix["ids"], n_items, n, int(mix["base_seed"]) + 1)
    rng = np.random.default_rng(seed + 1)
    return item_permutation(n_items, seed)[rng.permutation(base)]


def item_permutation(n_items: int, seed: int) -> np.ndarray:
    """Which class holds popularity rank i."""
    return np.random.default_rng([seed, 7]).permutation(n_items)


def check_sample(n_requests: int, size: int, seed: int) -> np.ndarray:
    """Sorted indices of the requests whose answers are compared."""
    rng = np.random.default_rng([seed, 11])
    size = min(size, n_requests)
    return np.sort(rng.choice(n_requests, size, replace=False))
