"""Percentiles, the same arithmetic everywhere."""
from __future__ import annotations

import statistics

import numpy as np


def percentile(xs, q: float) -> float | None:
    """Nearest-rank percentile ("higher": an actual sample with at least q% of
    the sample at or below it); None for an empty sample."""
    xs = np.asarray(list(xs), np.float64)
    if xs.size == 0:
        return None
    return float(np.percentile(xs, q, method="higher"))


def median(xs) -> float | None:
    xs = list(xs)
    return float(statistics.median(xs)) if xs else None
