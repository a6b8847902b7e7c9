"""Notes on a traced window, for reading its idle names.

  device_clock_lead_ms  how far the profiler's device plane sits early
                        against its host plane, seen from the launch
                        side. Each idle gap of the chip that is ended by
                        one of the program's own dispatches (a
                        ``repro.program.*`` annotation, the span of
                        ``Program.__call__``) gives one sample: the end
                        on the host of the dispatch that began after the
                        gap did, less the start of the first device op
                        after the gap. The note is the median. A sample
                        is the lead, plus the part of the call that runs
                        after the chip has started, less the chip's
                        launch latency: an estimate, not a bound.
  device_clock_lead_max_ms  the same lead seen from the waiting side:
                        the end of a host wait that returns once the
                        device has finished (``bdl.device_wait``,
                        ``decode.sync``) less the start of the idle gap
                        it ends in. The host sees the end only after it
                        happens, so a sample is the lead plus that
                        delay (and, for ``decode.sync``, the copy of the
                        heads to the host): an upper bound.
                        An idle gap not much longer than the lead cannot
                        be named by the host span open at its midpoint.
  device_s_by_scope     device seconds by the outermost ``push.*``
                        ``jax.named_scope`` in each op's detail (self
                        time, so a loop and its body count once); ops
                        with no such scope fall under ``unscoped``. Noted
                        only where some op's detail carries a scope.
"""
from __future__ import annotations

import bisect
import re
import statistics
from typing import Dict, Optional, Sequence, Tuple

from . import xtrace

DISPATCH_PREFIX = "repro.program."
WAITS = ("bdl.device_wait", "decode.sync")  # program spans that end when
#                                             the device has finished
SCOPE_RE = re.compile(r"(?:^|[/( ])(push\.[A-Za-z0-9_]+)")
MAX_LEAD_NS = 5e6     # a dispatch farther than this from a gap's end is
#                       not the one that ended it
MIN_GAP_NS = 1e5      # shorter gaps lie inside one program's run


def clock_lead_ns(ops, dispatches: Sequence[Tuple[str, float, float]],
                  max_ns: float = MAX_LEAD_NS, min_gap_ns: float = MIN_GAP_NS
                  ) -> Optional[float]:
    """Median of (dispatch end - start of the first device op after an
    idle gap) over gaps of at least ``min_gap_ns``. A gap is paired with
    the dispatch that began after the gap did (it reached an idle chip)
    and whose end lies nearest the gap's end, within ``max_ns``. ``ops``
    as (name, start, end, ...) tuples; ``dispatches`` as (name, start,
    end). None without a pair."""
    busy = xtrace.union((o[1], o[2]) for o in ops)
    by_end = sorted((d[2], d[1]) for d in dispatches)
    ends = [e for e, _ in by_end]
    samples = []
    for (_, g0), (g1, _) in zip(busy, busy[1:]):
        if g1 - g0 < min_gap_ns:
            continue
        lo = bisect.bisect_left(ends, g1 - max_ns)
        hi = bisect.bisect_right(ends, g1 + max_ns)
        near = [e for e, a in by_end[lo:hi] if a >= g0]
        if near:
            samples.append(min(near, key=lambda e: abs(e - g1)) - g1)
    return statistics.median(samples) if samples else None


def clock_lead_max_ns(ops, waits: Sequence[Tuple[str, float, float]],
                      max_ns: float = MAX_LEAD_NS,
                      min_gap_ns: float = MIN_GAP_NS) -> Optional[float]:
    """Median of (end of a host wait - start of the idle gap it ends in)
    over waits paired with a gap of at least ``min_gap_ns`` that began
    within ``max_ns`` of the wait's span. A wait returns only after the
    device has finished, so each sample is the lead plus the time the
    host took to see it (and any copy the wait includes): an upper
    estimate. ``waits`` as (name, start, end)."""
    busy = xtrace.union((o[1], o[2]) for o in ops)
    starts = [g0 for (_, g0), (g1, _) in zip(busy, busy[1:])
              if g1 - g0 >= min_gap_ns]
    samples = []
    for _, a, b in waits:
        k = bisect.bisect_right(starts, b) - 1
        if k >= 0 and starts[k] >= a - max_ns and b - starts[k] <= max_ns:
            samples.append(b - starts[k])
    return statistics.median(samples) if samples else None


def scope_of(detail: str) -> Optional[str]:
    m = SCOPE_RE.search(detail or "")
    return m.group(1) if m else None


def scope_seconds(ops, t0: float, t1: float) -> Dict[str, float]:
    """Device seconds in [t0, t1] by outermost ``push.*`` scope."""
    named = [(scope_of(o[3] if len(o) > 3 else "") or "unscoped", o[1], o[2])
             for o in ops]
    return {k: v * 1e-9 for k, v in xtrace.self_times(named, t0, t1).items()}


def note(cell):
    """Add both notes to a traced cell (nothing without a trace)."""
    r = cell.reduced
    if r is None or not r.devices:
        return
    dispatches = [h for h in r.host if h[0].startswith(DISPATCH_PREFIX)]
    waits = [s for s in r.spans if s[0] in WAITS]
    for key, fn, events in (("device_clock_lead_ms", clock_lead_ns,
                             dispatches),
                            ("device_clock_lead_max_ms", clock_lead_max_ns,
                             waits)):
        leads = [lead for lead in (fn(ops, events)
                                   for ops in r.devices.values())
                 if lead is not None]
        if leads:
            cell.notes[key] = statistics.mean(leads) * 1e-6
    scopes: Dict[str, float] = {}
    for ops in r.devices.values():
        for k, v in scope_seconds(ops, r.t0, r.t1).items():
            scopes[k] = scopes.get(k, 0.0) + v / len(r.devices)
    if set(scopes) - {"unscoped"}:
        cell.notes["device_s_by_scope"] = scopes
