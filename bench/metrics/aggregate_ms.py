"""Host time spent turning the fleet scan's sums into the sweep's rows:
the program's `fleet.result` and `sweep.aggregate` spans (`repro.obs`)."""
from bench.spans import total_ms

LAYER = "host aggregation"
UNIT = "ms"
MOVES = "container_epochs_per_s"
REQUIRED = ("fleet.result", "sweep.aggregate")


def read(ctx):
    return total_ms(ctx.trace, REQUIRED)
