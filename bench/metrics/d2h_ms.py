"""Host time spent pulling the sweep's outputs from the device: the
program's `plan.d2h` (with the region plan's build) and `fleet.d2h` spans
(`repro.obs`)."""
from bench.spans import total_ms

LAYER = "device to host transfer"
UNIT = "ms"
MOVES = "container_epochs_per_s"
REQUIRED = ("fleet.d2h",)
OPTIONAL = ("plan.d2h",)


def read(ctx):
    return total_ms(ctx.trace, REQUIRED, OPTIONAL)
