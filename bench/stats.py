"""Window arithmetic shared by the drivers, and the spread that the bounds
of ``BENCHMARK.json`` are set from.

``nearest_rank`` and ``poisson_arrivals`` are copies of
``repro.runtime.metrics.nearest_rank`` and ``repro.serve.loadgen
.poisson_arrivals``: the yardstick lives here, so a change to the program
cannot move it.
"""

from __future__ import annotations

import math
import statistics
from typing import Sequence

import numpy as np


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (rank ``ceil(q/100 * n)``, 1-based) of a
    non-empty sample; always an observed value."""
    ordered = sorted(values)
    n = len(ordered)
    if n == 0:
        raise ValueError("nearest_rank of an empty sample")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile must be in [0, 100], got {q!r}")
    return ordered[max(1, math.ceil(q / 100.0 * n)) - 1]


def poisson_arrivals(rate_rps: float, n: int, seed: int) -> np.ndarray:
    """Arrival offsets (seconds from t0) of a Poisson process; the same seed
    gives the same schedule."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be positive, got {rate_rps}")
    if n < 0:
        raise ValueError(f"n must be non-negative, got {n}")
    gaps = np.random.default_rng(seed).exponential(1.0 / rate_rps, size=n)
    return np.cumsum(gaps)


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
