"""Kernels (``kernels/paged_decode_attention``): least time the chip
needs for the paged decode attention of the window, over the kernel's
device time in the trace.

The least time is the larger of FLOPs over peak FLOP/s and bytes over
HBM bandwidth, both counted from shapes by the configuration reference's
``paged_attn_cost`` for the positions every decoded token attended to
(the K/V of live positions, each query and its output, every layer,
every particle); which bound applies is noted on standard error."""
from bench.core import peaks


def read(cell):
    if cell.reduced is None:
        return None
    k_s, n = cell.reduced.op_seconds("paged_decode_attention")
    tokens = cell.layer.get("decode_tokens", 0)
    if n == 0 or k_s <= 0 or not tokens:
        return None
    f, b = cell.r.reference.paged_attn_cost(cell.spec,
                                            cell.layer["decode_ctx"], tokens)
    p = cell.layer["particles"]
    least, bound = peaks.roofline_seconds(f * p, b * p, peaks.peaks(
        cell.devices[0].device_kind))
    cell.notes["paged_attn_bound"] = bound
    cell.notes["paged_attn_kernel_s"] = k_s
    cell.notes["paged_attn_calls"] = n
    return 100.0 * least / k_s
