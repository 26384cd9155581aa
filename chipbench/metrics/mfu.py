"""Model FLOP utilization: tgs x model FLOPs per token (flops/model.py,
recompute not counted) over the bf16 peak of the device kind."""
UNIT, BETTER, SOURCE = "%", "higher", "host_clock"


def read(ctx):
    tgs = ctx.metric("tgs")
    if tgs is None:
        return None
    return 100.0 * tgs * ctx.flops_per_token / ctx.peaks["bf16_flops"]
