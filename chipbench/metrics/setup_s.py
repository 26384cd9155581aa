"""Set-up seconds: process start to the window's first step: imports,
weights made on the device, the step compiled (or found in the
persistent cache), the first steps that the check compares."""
UNIT, BETTER, SOURCE = "s", "lower", "host_clock"


def read(ctx):
    return ctx.setup_s
