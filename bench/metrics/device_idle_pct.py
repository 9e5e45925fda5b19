"""Share of the traced sweep's wall time in which no operation ran on the
device: 1 - (union of busy intervals) / window."""
from bench.trace import busy_s

LAYER = "device"
UNIT = "%"
MOVES = "container_epochs_per_s"


def read(ctx):
    w = ctx.trace.window_s
    return 100.0 * (1.0 - busy_s(ctx.trace) / w) if w > 0 else None
