"""The benchmark's arithmetic on samples: nearest-rank percentiles, the
quartile spread the bounds are set from, and the two readings of it a check
holds a bound to."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(samples: Sequence[float], p: float) -> float:
    """Nearest rank: the smallest sample at or above p% of the samples (the
    same index as the program's `utils/metrics.percentile_of_sorted`)."""
    ordered = sorted(samples)
    if not ordered:
        raise ValueError("percentile of no samples")
    return ordered[min(len(ordered) - 1,
                       max(0, math.ceil(len(ordered) * p / 100.0) - 1))]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as `statistics.quantiles(values, n=4)` gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values: Sequence[float]) -> list:
    """The runs less the one farthest from their median."""
    kept = list(values)
    middle = statistics.median(kept)
    kept.remove(max(kept, key=lambda v: abs(v - middle)))
    return kept


def trimmed_range(values: Sequence[float]) -> float:
    """The distance between the extreme runs once the farthest from the
    median is left out, over the median of all: what separates the two
    sides of a pair of sets, where `spread` is the distance between their
    quartiles."""
    kept = without_farthest(values)
    return (max(kept) - min(kept)) / statistics.median(values)


def tight(sets: Sequence[Sequence[float]]) -> float:
    """What a check holds to HALF of a bound: the mean over the sets of
    each set's spread without its farthest run."""
    return statistics.fmean(spread(without_farthest(s)) for s in sets)


def wide(sets: Sequence[Sequence[float]]) -> float:
    """What a check holds a bound to EIGHT times of: the spread of all the
    runs of all the sets."""
    return spread([v for s in sets for v in s])
