"""Fused training call (``bdl/infer.py``, ``_fused_epochs``): host
milliseconds of each ``bdl.fused_call`` span inside the window that lie
outside its ``bdl.epoch`` spans and its ``bdl.device_wait`` (the host
dispatches an epoch's steps ahead of the device, then waits for it):
plan, checkout, commit, the per-slot loss reads; averaged over the
calls. Also notes the part of it in ``bdl.loss_sync``, the device
clock's lead and device seconds by scope
(``bench/core/device_notes.py``)."""
from bench.core import device_notes


def _inside(spans, name, a, b):
    return sum(y - x for n, x, y in spans if n == name and a <= x and y <= b)


def read(cell):
    device_notes.note(cell)
    spans = cell.layer.get("program_spans")
    if not spans:
        return None
    t0, t1 = cell.t_w0, cell.t_w1
    calls = [(a, b) for n, a, b in spans
             if n == "bdl.fused_call" and t0 <= a and b <= t1]
    if not calls:
        return None
    over = [(b - a) - _inside(spans, "bdl.epoch", a, b)
            - _inside(spans, "bdl.device_wait", a, b) for a, b in calls]
    sync = [_inside(spans, "bdl.loss_sync", a, b) for a, b in calls]
    cell.notes["loss_sync_ms_per_call"] = 1e3 * sum(sync) / len(calls)
    cell.notes["fused_calls_in_window"] = len(calls)
    return 1e3 * sum(over) / len(calls)
