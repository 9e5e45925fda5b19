"""Host time spent pushing the sweep's inputs to the device and waiting
for them to land: the program's `plan.h2d` and `fleet.h2d` spans
(`repro.obs`), the region plan's where the cell places containers."""
from bench.spans import total_ms

LAYER = "host to device transfer"
UNIT = "ms"
MOVES = "container_epochs_per_s"
REQUIRED = ("fleet.h2d",)
OPTIONAL = ("plan.h2d",)


def read(ctx):
    return total_ms(ctx.trace, REQUIRED, OPTIONAL)
