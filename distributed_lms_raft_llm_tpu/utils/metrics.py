"""Lightweight serving metrics: counters + latency histograms.

The reference has no observability beyond ~80 print() call sites
(SURVEY.md §5). The BASELINE north-star metric is p50 TTFT per student
query, so latency percentiles are first-class here: every RPC entry point
records into a histogram, and servers log/export snapshots.

Thread-safe, dependency-free; values are plain floats so snapshots can be
JSON-serialized straight into logs or the bench harness.
"""

from __future__ import annotations

import bisect
import math
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

from .locks import make_lock


def percentile_of_sorted(samples: Sequence[float], p: float) -> float:
    """Nearest-rank percentile of an ascending-sorted sequence: the
    smallest sample ranked at or above p% of the distribution.

    The ONE quantile index formula in the repo. `LatencyHistogram`
    (percentile + snapshot), `sim/slo.stage_breakdown`, and the telemetry
    timeline's windowed percentiles all share it, so small-n behavior
    agrees everywhere: p50 of a 2-sample set is the FIRST sample
    (ceil(0.5*2)-1 == 0), not the max — the old per-call-site `n // 2` /
    `int(n * p / 100)` formulas disagreed exactly there.
    """
    n = len(samples)
    if n == 0:
        raise ValueError("percentile of an empty sequence")
    idx = min(n - 1, max(0, math.ceil(n * p / 100.0) - 1))
    return samples[idx]


class LatencyHistogram:
    """Reservoir of recent latencies with percentile queries.

    Alongside the centered reservoir (all-time percentiles), a small
    time-stamped ring of the most recent observations backs
    `window_percentile` — the true sliding-window quantile the continuous
    SLO engine (sim/slo.py) evaluates burn rates against, which a
    cumulative reservoir cannot answer (an early spike would hold the
    all-time p95 up forever).
    """

    def __init__(self, max_samples: int = 4096, recent: int = 4096):
        self._samples: List[float] = []  # guarded-by: _lock
        self._max = max_samples
        self._count = 0                  # guarded-by: _lock
        self._total = 0.0                # guarded-by: _lock
        # (monotonic time, value) of the newest observations, for
        # windowed quantiles; bounded so observe() stays O(log n).
        self._recent: Deque[Tuple[float, float]] = deque(  # guarded-by: _lock
            maxlen=recent
        )
        self._lock = make_lock("LatencyHistogram._lock")

    def observe(self, seconds: float) -> None:
        with self._lock:
            self._count += 1
            self._total += seconds
            self._recent.append((time.monotonic(), seconds))
            bisect.insort(self._samples, seconds)
            if len(self._samples) > self._max:
                # Drop alternating extremes to keep the reservoir centered.
                self._samples.pop(0 if self._count % 2 else -1)

    def percentile(self, p: float) -> Optional[float]:
        with self._lock:
            if not self._samples:
                return None
            return percentile_of_sorted(self._samples, p)

    def window_percentile(self, window_s: float, p: float,
                          now: Optional[float] = None) -> Optional[float]:
        """Percentile of the observations from the last `window_s`
        seconds (None when the window is empty — distinct from 0.0).
        Bounded by the recent ring: under extreme rates the window may
        cover fewer observations than arrived, never more."""
        cutoff = (now if now is not None else time.monotonic()) - window_s
        with self._lock:
            vals = sorted(v for t, v in self._recent if t >= cutoff)
        if not vals:
            return None
        return percentile_of_sorted(vals, p)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            n = len(self._samples)
            if n == 0:
                return {"count": 0, "samples": 0}
            return {
                "count": self._count,
                # Reservoir size the percentiles below are computed from
                # (== count until the reservoir wraps at max_samples):
                # readers can judge how trustworthy a p95/p99 is.
                "samples": n,
                "mean_s": self._total / self._count,
                "p50_s": percentile_of_sorted(self._samples, 50),
                "p90_s": percentile_of_sorted(self._samples, 90),
                # p95 is the SLO percentile the semester simulator (sim/)
                # asserts from /metrics, so it ships in every snapshot.
                "p95_s": percentile_of_sorted(self._samples, 95),
                "p99_s": percentile_of_sorted(self._samples, 99),
                "max_s": self._samples[-1],
            }


class Metrics:
    """Named counters + histograms + gauges; one per server process."""

    def __init__(self):
        self._counters: Dict[str, int] = {}          # guarded-by: _lock
        self._hists: Dict[str, LatencyHistogram] = {}  # guarded-by: _lock
        self._gauges: Dict[str, float] = {}          # guarded-by: _lock
        # Named for the live acquisition-order graph (utils/locks.py).
        self._lock = make_lock("Metrics._lock")

    def set_gauge(self, name: str, value: float) -> None:
        """Last-value gauge for dimensionless readings (ratios, sizes) —
        NOT latencies: histogram snapshots are rendered with seconds
        suffixes, so a unitless value there reads as a bogus latency."""
        with self._lock:
            self._gauges[name] = float(value)

    def inc(self, name: str, amount: int = 1) -> int:
        """Add to a counter; returns its new total (so a ratio gauge can
        be computed from the counters it is a ratio of)."""
        with self._lock:
            total = self._counters[name] = (
                self._counters.get(name, 0) + amount
            )
        return total

    def hist(self, name: str) -> LatencyHistogram:
        with self._lock:
            if name not in self._hists:
                self._hists[name] = LatencyHistogram()
            return self._hists[name]

    def time(self, name: str) -> "_Timer":
        return _Timer(self.hist(name))

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            counters = dict(self._counters)
            hists = {k: h.snapshot() for k, h in self._hists.items()}
            gauges = dict(self._gauges)
        out = {"counters": counters, "latency": hists}
        if gauges:
            out["gauges"] = gauges
        return out


class _Timer:
    def __init__(self, hist: LatencyHistogram):
        self._hist = hist

    def __enter__(self):
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self._hist.observe(time.monotonic() - self._t0)
        return False
