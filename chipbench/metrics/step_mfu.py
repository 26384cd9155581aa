"""The whole step's share of the chip's bf16 peak over the traced
window: model FLOPs of the traced steps (flops/model.py) over the
window's length on the trace's clock, the cell's chips and the peak.
It bounds every kernel's gain: a kernel taken off the path leaves its
roofline silent, not this."""
from chipbench import tracing

LAYER, UNIT, BETTER, SOURCE, MOVES = ("model step", "%", "higher",
                                      "device_trace", "tgs")


def read(ctx):
    if not ctx.trace or not ctx.steps:
        return None
    lo, hi = tracing.window(ctx.trace)
    work = ctx.steps * ctx.tokens_per_step * ctx.flops_per_token
    return 100.0 * work / ((hi - lo) / 1e9) / ctx.chips \
        / ctx.peaks["bf16_flops"]
