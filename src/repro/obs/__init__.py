# Unified observability: tracing, metrics, device gauges, cost profiling.
# clock.py   — the ONE timebase (perf_counter) every subsystem stamps
# trace.py   — thread-safe bounded-ring span recorder, compiled-out when
#              disabled; instruments executor / store / cache / serve /
#              decode / bdl (span taxonomy: DESIGN.md §12)
# metrics.py — latency Histogram + the one percentile implementation
#              behind every latency_p* stats key
# device.py  — per-device memory gauges, store/page-pool occupancy,
#              per-Program FLOPs/bytes cost attribution (hlo_cost +
#              compiled.cost_analysis)
# export.py  — Chrome/Perfetto trace-event JSON + Prometheus text
from typing import Any, Dict

# device.py is imported lazily (inside Obs): it pulls in jax, and the
# core executor — which is deliberately jax-free — imports this package
# for clock + trace on its hot path
from . import clock, export, metrics, trace


def summary() -> Dict[str, Any]:
    """The ``pd.stats()["obs"]`` section: the tracer's state."""
    c = trace.TRACER.counts()
    return {
        "tracing_enabled": trace.TRACER.enabled,
        "spans_recorded": c["recorded"],
        "spans_buffered": c["buffered"],
        "spans_dropped": c["dropped"],
        "ring": trace.TRACER.ring,
        "clock": "perf_counter",
    }


class Obs:
    """``pd.obs()`` front-end: one handle for snapshot / dump / export.

        pd.obs().snapshot()             # stats + devices + program costs
        pd.obs().dump_trace("t.json")   # open at ui.perfetto.dev
        pd.obs().prometheus()           # text exposition for a scrape
    """

    def __init__(self, pd):
        self.pd = pd

    def snapshot(self, *, costs: bool = False) -> Dict[str, Any]:
        """Everything at once: the unified stats dict, device gauges,
        store occupancy, per-program cost attribution (``costs=True``
        forces the lazy FLOPs/bytes analysis per cache entry), and the
        tracer's counters."""
        from . import device
        return {
            "stats": self.pd.stats(),
            "devices": device.device_gauges(),
            "store": device.store_gauges(self.pd.store),
            "programs": self.pd.runtime.cache.program_costs(compute=costs),
            "trace": trace.TRACER.counts(),
        }

    def chrome_trace(self) -> Dict[str, Any]:
        return export.chrome_trace()

    def dump_trace(self, path: str) -> str:
        return export.dump_chrome_trace(path)

    def prometheus(self) -> str:
        return export.prometheus_text(self.pd.stats())
