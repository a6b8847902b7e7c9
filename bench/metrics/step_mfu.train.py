"""Model step (``bdl/`` fused ensemble step, ``models/vit``): training
model FLOPs from shapes (the configuration reference's ``train_flops``)
for every particle and step in the window, per second of the window,
over the chips' bf16 peak."""
from bench.core import peaks


def read(cell):
    steps = cell.layer.get("steps")
    if not steps:
        return None
    work = cell.r.reference.train_flops(cell.spec, cell.layer["batch"]) \
        * cell.layer["particles"] * steps
    peak = peaks.peaks(cell.devices[0].device_kind)["flops"]
    return 100.0 * work / (cell.window_s * cell.chips * peak)
