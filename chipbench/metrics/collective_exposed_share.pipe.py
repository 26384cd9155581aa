"""Share of the traced window in which a collective (collective-permute,
all-reduce, ...) runs on a device and no compute op does, mean over the
pipeline's devices."""
from chipbench import tracing

LAYER, UNIT, BETTER, SOURCE, MOVES = ("pipeline runtime", "%", "lower",
                                      "device_trace", "tgs")


def read(ctx):
    if not ctx.trace or len(ctx.trace["devices"]) < 2:
        return None
    return 100.0 * tracing.exposed_collective_share(ctx.trace)
