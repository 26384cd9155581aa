"""Tokens trained per second per chip: every token of every step of the
window, over the window's seconds (host clock, ending in
``block_until_ready``), over the cell's chips.  The paper's TGS."""
UNIT, BETTER, SOURCE = "tokens/s/chip", "higher", "host_clock"


def read(ctx):
    if not ctx.steps:
        return None
    return ctx.steps * ctx.tokens_per_step / ctx.window_s / ctx.chips
