"""Serve scheduler (``serve/batcher.DecodeScheduler``): host time of the
program's ``decode.prefill`` spans over that of ``decode.prefill`` and
``decode.step`` spans together, inside the window."""


def read(cell):
    spans = cell.layer.get("program_spans")
    if not spans:
        return None
    t0, t1 = cell.t_w0, cell.t_w1
    acc = {"decode.prefill": 0.0, "decode.step": 0.0}
    for name, a, b in spans:
        if name in acc:
            acc[name] += max(0.0, min(b, t1) - max(a, t0))
    both = acc["decode.prefill"] + acc["decode.step"]
    return None if both <= 0 else 100.0 * acc["decode.prefill"] / both
