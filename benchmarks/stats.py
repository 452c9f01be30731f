"""The benchmark's arithmetic on samples: nearest-rank percentiles and the
quartile spread the bounds are set from."""

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
