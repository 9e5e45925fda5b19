"""Host seconds spent making the cell's inputs from the seed."""

LAYER = "set-up: input generation"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return ctx.setup["gen_s"]
