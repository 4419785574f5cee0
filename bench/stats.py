"""Quantiles and spreads used by the benchmark."""
from __future__ import annotations

import statistics
from typing import Sequence


def nearest_rank(sorted_vals: Sequence[float], q: float) -> float:
    """Nearest-rank quantile of an ascending sequence: the value at index
    ``min(n - 1, int(n * q))`` (the arithmetic of
    ``repro.serve.telemetry.nearest_rank``, copied so that the yardstick
    does not move with the program)."""
    n = len(sorted_vals)
    if n == 0:
        raise ValueError("nearest_rank of an empty sequence")
    return sorted_vals[min(n - 1, int(n * q))]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median,
    as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
