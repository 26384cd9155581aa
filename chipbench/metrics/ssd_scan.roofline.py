"""Share of its roofline that the ``ssd_scan`` kernel reaches:
the least time of its calls' work (flops/ssd_scan.py) at the
peaks of the device kind, over the summed device time of its events."""
from chipbench import tracing

LAYER, UNIT, BETTER, SOURCE, MOVES = ("kernels", "%", "higher",
                                      "device_trace", "tgs")
KERNEL = "ssd_scan"


def read(ctx):
    if not ctx.trace:
        return None
    n, ns = tracing.kernel_events(ctx.trace, KERNEL)
    if not n or ns <= 0:
        return None
    flops, nbytes = ctx.flops(KERNEL).work(ctx.model, ctx.kernel_batch,
                                           ctx.seq)
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / (ns / 1e9)
