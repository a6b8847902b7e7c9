"""Runtime (``runtime/compiles.py``): seconds of the window in which the
process traced, lowered or compiled a program (a persistent-cache load
included), the union of the program's ``runtime.compile`` spans. Set-up
warms the buckets the traffic's prompts use, so this reads what the
window had to compile besides. None for a program without the compile
listener (no ``repro.runtime.compiles`` among the modules it ran)."""
import sys

from bench.core import xtrace


def read(cell):
    spans = cell.layer.get("program_spans")
    if spans is None or "repro.runtime.compiles" not in sys.modules:
        return None
    return xtrace.total(xtrace.clip(xtrace.union(
        (a, b) for n, a, b in spans if n == "runtime.compile"),
        cell.t_w0, cell.t_w1))
