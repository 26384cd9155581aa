"""Device bytes the compiled step that the window runs needs, in GB:
arguments + outputs + temporaries - aliased (donated) bytes, from its
``memory_analysis()``, the most on any of the cell's devices."""
LAYER, UNIT, BETTER, SOURCE, MOVES = ("model step", "GB", "lower",
                                      "program_counter", "tgs")


def read(ctx):
    if not ctx.memory:
        return None
    m = ctx.memory
    return (m["argument"] + m["output"] + m["temp"] - m["alias"]) / 1e9
