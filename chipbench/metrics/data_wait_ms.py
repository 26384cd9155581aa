"""Host milliseconds per step spent in ``next(loader)`` (the program's
``data.pipeline`` loader: prefetch queue and device_put), mean over the
traced steps."""
LAYER, UNIT, BETTER, SOURCE, MOVES = ("data", "ms", "lower", "host_clock",
                                      "tgs")


def read(ctx):
    if not ctx.data_waits:
        return None
    return 1e3 * sum(ctx.data_waits) / len(ctx.data_waits)
