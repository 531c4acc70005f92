"""Traffic drawn from the seed.

Open loop: a Poisson process at a fixed rate, conditioned on its count
(``round(rate x seconds)`` arrivals placed uniformly and sorted), so every
seed offers the same amount of work in another order.  Each request
carries a row of the test set drawn uniformly; tenants take turns by
request id.  Closed loop: the rows only; the client loop keeps its
backlog full.
"""
from __future__ import annotations

import numpy as np


def streams(seed: int, n: int) -> list[np.random.Generator]:
    """``n`` independent generators for one run's seed."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(int(seed)).spawn(n)]


def sub_seed(seed: int, purpose: int) -> int:
    """A 31-bit seed for ``purpose`` (jax keys hold 32 bits)."""
    ss = np.random.SeedSequence([int(seed), int(purpose)])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def open_schedule(rng: np.random.Generator, rate: float, seconds: float,
                  n_rows: int):
    """(due offsets [n] float64 ascending in [0, seconds), rows [n])."""
    n = int(round(rate * seconds))
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    return due, rng.integers(0, n_rows, size=n).astype(np.int64)


def row_stream(rng: np.random.Generator, n_rows: int, n: int) -> np.ndarray:
    return rng.integers(0, n_rows, size=n).astype(np.int64)
