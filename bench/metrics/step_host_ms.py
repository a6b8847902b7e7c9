"""Serve scheduler (``serve/batcher.DecodeScheduler``): host milliseconds
per decode step spent outside the device's work, from the program's own
phase spans inside the window: packing the step input (``decode.pack``),
dispatching it (``decode.dispatch``: host to device copy and launch) and
appending and retiring after it (``decode.emit``), over the window's
scheduler steps. Reads every ``step_host_ms.<cell kind>`` metric; also
notes the device clock's lead and device seconds by scope
(``bench/core/device_notes.py``)."""
from bench.core import device_notes

PHASES = ("decode.pack", "decode.dispatch", "decode.emit")


def span_seconds(cell, names) -> float:
    """Host seconds of the named program spans, clipped to the window."""
    t0, t1 = cell.t_w0, cell.t_w1
    return sum(max(0.0, min(b, t1) - max(a, t0))
               for n, a, b in cell.layer.get("program_spans", ())
               if n in names)


def read(cell):
    device_notes.note(cell)
    steps = cell.layer.get("steps")
    if not steps or not cell.layer.get("program_spans"):
        return None
    host = span_seconds(cell, PHASES)
    if host <= 0:
        return None
    cell.notes["sync_ms_per_step"] = 1e3 * span_seconds(
        cell, ("decode.sync",)) / steps
    return 1e3 * host / steps
