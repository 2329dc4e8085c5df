"""Percentile and mean arithmetic of the benchmark.

A copy, not an import: the quantile rule is the one of
``repro.serve.metrics.latency_summary`` (sorted sample, nearest rank at
``round(p * (n - 1))``), kept here so that a change to the program cannot
move the yardstick.
"""
from __future__ import annotations

import math
from typing import Sequence


def quantile(values: Sequence[float], p: float) -> float:
    """Nearest-rank quantile of a sample; a failed request enters as
    ``math.inf`` and so lands in the tail. Raises on an empty sample."""
    xs = sorted(float(x) for x in values)
    if not xs:
        raise ValueError("quantile of an empty sample")
    return xs[min(len(xs) - 1, int(round(p * (len(xs) - 1))))]


def mean(values: Sequence[float]) -> float:
    xs = [float(x) for x in values]
    if not xs:
        raise ValueError("mean of an empty sample")
    return math.fsum(xs) / len(xs)
