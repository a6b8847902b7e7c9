"""Serve scheduler (``serve/batcher.DecodeScheduler``): share of the
window spent replaying preempted sequences, the program's
``decode.replay`` spans (a re-admission's prefill, a compile at its
first call of a bucket included) over the window. A program that
records no ``decode.emit`` span does not record replays either: None."""


def read(cell):
    spans = cell.layer.get("program_spans")
    if not spans or not any(n == "decode.emit" for n, _, _ in spans):
        return None
    t0, t1 = cell.t_w0, cell.t_w1
    replay = sum(max(0.0, min(b, t1) - max(a, t0))
                 for n, a, b in spans if n == "decode.replay")
    cell.notes["replays_in_window"] = sum(
        1 for n, a, b in spans if n == "decode.replay" and t0 <= a <= t1)
    return 100.0 * replay / cell.window_s
