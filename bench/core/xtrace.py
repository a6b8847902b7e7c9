"""From a profiler trace to device metrics.

``load`` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``, nothing
else) into plain lists:

  device ops   per device plane (``/device:TPU:n``), the events of its
               "XLA Ops" line: (name, start_ns, end_ns, detail), where
               detail joins the event's string stats (HLO long name,
               source op name) so a kernel can be found by its name;
  host spans   the events of every host thread line:
               (name, start_ns, end_ns).

Everything after that works on those lists and is checked against
hand-built ones in the tests:

  union          merged busy intervals;
  busy_ns        device time in which some operation ran, inside a window;
  self_times     device time per operation, less that of nested ops;
  exposed_ns     time in which a collective runs and nothing else does;
  gaps           idle intervals of a window;
  attribute      each idle interval named by the innermost host span open
                 at its midpoint ("no host span" when none is).

Host and device events of one trace share the profiler's clock. The
harness's own clock (``time.perf_counter``) is mapped onto it with an
anchor event it records at a known perf_counter time (``ANCHOR``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANCHOR = "bench.anchor"
OPS_LINE = "XLA Ops"
COLLECTIVE_RE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce|reducescatter|collectivepermute|alltoall",
    re.IGNORECASE)

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals (touching ones merge)."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], t0: float, t1: float
         ) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def busy_ns(op_intervals: Iterable[Interval], t0: float, t1: float) -> float:
    """Time in [t0, t1] in which at least one operation ran."""
    return total(clip(union(op_intervals), t0, t1))


def gaps(op_intervals: Iterable[Interval], t0: float, t1: float
         ) -> List[Interval]:
    """Idle intervals of [t0, t1]."""
    out, reach = [], t0
    for a, b in clip(union(op_intervals), t0, t1):
        if a > reach:
            out.append((reach, a))
        reach = max(reach, b)
    if t1 > reach:
        out.append((reach, t1))
    return out


def intersect_total(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            acc += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return acc


def exposed_ns(ops, t0: float, t1: float) -> Tuple[float, float]:
    """(collective time, collective time with no other operation running)
    inside [t0, t1]; ``ops`` as (name, start, end, ...) tuples."""
    coll = union(clip([(o[1], o[2]) for o in ops
                       if COLLECTIVE_RE.search(o[0])], t0, t1))
    comp = union(clip([(o[1], o[2]) for o in ops
                       if not COLLECTIVE_RE.search(o[0])], t0, t1))
    c = total(coll)
    return c, c - intersect_total(coll, comp)


def short_name(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def self_times(ops, t0: float, t1: float) -> Dict[str, float]:
    """Device time per operation name inside [t0, t1], each op's time
    less that of the ops nested inside it (a loop and its body count
    once)."""
    acc: Dict[str, float] = defaultdict(float)
    evs = sorted(((max(o[1], t0), min(o[2], t1), short_name(o[0]))
                  for o in ops if min(o[2], t1) > max(o[1], t0)),
                 key=lambda e: (e[0], -e[1]))
    stack: List[list] = []          # [start, end, name, child time]

    def close(until):
        while stack and stack[-1][1] <= until:
            a, b, n, kids = stack.pop()
            acc[n] += (b - a) - kids
            if stack:
                stack[-1][3] += b - a

    for a, b, n in evs:
        close(a)
        stack.append([a, b, n, 0.0])
    close(float("inf"))
    return dict(acc)


def matching(ops, pattern: str, t0: float, t1: float) -> Tuple[float, int]:
    """(device time, count) of the operations whose name or detail
    contains ``pattern``."""
    t, n = 0.0, 0
    for o in ops:
        detail = o[3] if len(o) > 3 else ""
        if pattern in o[0] or pattern in detail:
            a, b = max(o[1], t0), min(o[2], t1)
            if b > a:
                t += b - a
                n += 1
    return t, n


def attribute(idle: Sequence[Interval], spans: Sequence[Tuple[str, float, float]]
              ) -> Dict[str, float]:
    """Idle time by the innermost host span open at each gap's midpoint
    (the latest-starting one that covers it)."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    acc: Dict[str, float] = defaultdict(float)
    for a, b in idle:
        mid = 0.5 * (a + b)
        k = bisect.bisect_right(starts, mid)
        name = "no host span"
        for s in reversed(spans[max(0, k - 4096):k]):
            if s[2] >= mid:
                name = s[0]
                break
        acc[name] += b - a
    return dict(acc)


# ---------------------------------------------------------------------------
# reading a trace file
# ---------------------------------------------------------------------------

def _detail(ev) -> str:
    parts = []
    try:
        for k, v in ev.stats:
            if isinstance(v, str):
                parts.append(v)
    except Exception:
        pass
    return " ".join(parts)


def load(path: str):
    """(devices, host) from an ``.xplane.pb``: devices maps a plane name
    to its op tuples (name, start_ns, end_ns, detail); host is a list of
    (name, start_ns, end_ns) from every host line."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:") and "CPU" not in name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         _detail(e)) for e in line.events]
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
    return devices, host


def anchor_ns(host) -> Optional[float]:
    """Start of the anchor event on the profiler's clock."""
    for name, a, _ in host:
        if name == ANCHOR:
            return a
    return None


class Reduced:
    """One traced window, reduced. Times in seconds."""

    def __init__(self, devices, host, t0_ns: float, t1_ns: float,
                 spans=()):
        self.devices = devices
        self.t0, self.t1 = t0_ns, t1_ns
        self.window_s = (t1_ns - t0_ns) * 1e-9
        self.spans = list(spans)          # (name, start_ns, end_ns)
        self.host = host

    def busy_s(self) -> float:
        """Busy seconds averaged over the device planes."""
        if not self.devices:
            return 0.0
        return sum(busy_ns([(o[1], o[2]) for o in ops], self.t0, self.t1)
                   for ops in self.devices.values()) * 1e-9 / len(self.devices)

    def idle_share(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, pattern: str) -> Tuple[float, int]:
        """Device seconds (summed over devices) and count of operations
        whose name or detail contains ``pattern``."""
        t, n = 0.0, 0
        for ops in self.devices.values():
            dt, dn = matching(ops, pattern, self.t0, self.t1)
            t += dt
            n += dn
        return t * 1e-9, n

    def exposed_collective_s(self) -> Tuple[float, float]:
        """(collective, exposed collective) seconds averaged over devices."""
        if not self.devices:
            return 0.0, 0.0
        c = e = 0.0
        for ops in self.devices.values():
            dc, de = exposed_ns(ops, self.t0, self.t1)
            c += dc
            e += de
        k = len(self.devices) * 1e9
        return c / k, e / k

    def top_ops(self, n: int = 10):
        acc: Dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            for k, v in self_times(ops, self.t0, self.t1).items():
                acc[k] += v
        k = max(1, len(self.devices))
        return [[name, t * 1e-9 / k] for name, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10):
        acc: Dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            idle = gaps([(o[1], o[2]) for o in ops], self.t0, self.t1)
            for k, v in attribute(idle, self.spans).items():
                acc[k] += v
        k = max(1, len(self.devices))
        return [[name, t * 1e-9 / k] for name, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
