"""Serve scheduler (``serve/batcher.DecodeScheduler``): milliseconds of
the window per decode step, from the scheduler's step counter."""


def read(cell):
    steps = cell.layer.get("steps")
    if not steps:
        return None
    return 1e3 * cell.window_s / steps
