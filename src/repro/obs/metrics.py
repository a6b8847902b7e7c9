"""obs.metrics — latency distributions: ``Histogram`` and the one
``percentile`` implementation.

The serving layer's three duplicated latency implementations
(``service.percentile`` + two hand-rolled ``_latencies`` ring deques in
batcher.py) collapse onto ``Histogram`` — same ring bound, same
``np.percentile`` semantics, one implementation. Counters live in the
``pd.stats()`` sections themselves (their keys are asserted by
tests/test_obs.py), and the Prometheus exporter renders that snapshot.

Histograms keep a bounded ring of raw observations (default 4096 — the
serving layer's historical ``_LAT_RING``) so percentiles are exact over
the recent window, plus lifetime count/sum for rate math.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, List

import numpy as np

DEFAULT_RING = 4096


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (``np.percentile`` semantics; q in
    [0, 100]); 0.0 on empty input. The single implementation behind
    every latency_p* stats key in the repo."""
    xs = np.asarray(list(xs) if not isinstance(xs, np.ndarray) else xs)
    if xs.size == 0:
        return 0.0
    return float(np.percentile(xs, q))


class Histogram:
    """Bounded-ring distribution: exact percentiles over the last
    ``ring`` observations, lifetime count/sum. ``observe`` is lock-free
    for the same reason the tracer's record is (bounded-deque append is
    atomic; count/sum are best-effort under concurrent writers, exact
    under the single pump threads that own them here)."""
    __slots__ = ("name", "_ring", "count", "sum")

    def __init__(self, name: str, ring: int = DEFAULT_RING):
        self.name = name
        self._ring: deque = deque(maxlen=ring)
        self.count = 0
        self.sum = 0.0

    def observe(self, v: float):
        self._ring.append(v)
        self.count += 1
        self.sum += v

    def values(self) -> List[float]:
        return list(self._ring)

    def percentile(self, q: float) -> float:
        return percentile(list(self._ring), q)

    def snapshot(self) -> Dict[str, float]:
        xs = list(self._ring)
        return {"count": self.count, "sum": self.sum,
                "p50": percentile(xs, 50), "p95": percentile(xs, 95),
                "p99": percentile(xs, 99)}
