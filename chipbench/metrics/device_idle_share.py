"""Share of the traced window in which no operation runs on the device:
1 - (union of device op intervals / window), mean over the cell's
devices."""
from chipbench import tracing

LAYER, UNIT, BETTER, SOURCE, MOVES = ("device", "%", "lower",
                                      "device_trace", "tgs")


def read(ctx):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    return 100.0 * (1.0 - tracing.busy_share(ctx.trace))
