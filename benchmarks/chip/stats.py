"""Percentiles over a run's stamps, and the spread of a metric over runs."""
from __future__ import annotations

import statistics

import numpy as np


def percentile(values, p: float) -> float | None:
    """The ``p``-th percentile (numpy's linear interpolation) of every value;
    None for none."""
    xs = np.asarray(list(values), float)
    if xs.size == 0:
        return None
    return float(np.percentile(xs, p))


def spread(values) -> float:
    """Distance between the first and third quartile, as a share of the
    median (Python's ``statistics.quantiles``, the driver's definition)."""
    xs = list(values)
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
