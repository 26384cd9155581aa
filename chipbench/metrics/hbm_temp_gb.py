"""Temporary bytes of the compiled step that the window runs
(``memory_analysis().temp_size_in_bytes``), in GB, the most on any of
the cell's devices."""
LAYER, UNIT, BETTER, SOURCE, MOVES = ("model step", "GB", "lower",
                                      "program_counter", "tgs")


def read(ctx):
    return ctx.memory["temp"] / 1e9 if ctx.memory else None
