"""Model step (``models/api.decode_step_paged`` and ``prefill_paged``):
model FLOPs from shapes (the configuration reference's counts) of every
token served in the window (decoded tokens with the positions they
attended to, plus the prompts prefilled), for every particle, per second
of the window, over the chip's bf16 peak."""
from bench.core import peaks


def read(cell):
    tokens = cell.layer.get("decode_tokens")
    if not tokens:
        return None
    spec, ref = cell.spec, cell.r.reference
    work = tokens * (ref.matmul_flops_per_token(spec)
                     + ref.head_flops(spec)) \
        + ref.attn_flops(spec, cell.layer["decode_ctx"]) \
        + sum(ref.prefill_flops(spec, n)
              for n in cell.layer.get("prefill_lens", ()))
    work *= cell.layer["particles"]
    peak = peaks.peaks(cell.devices[0].device_kind)["flops"]
    return 100.0 * work / (cell.window_s * cell.chips * peak)
