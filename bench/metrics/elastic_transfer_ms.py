"""Host time spent moving the elasticity scan's inputs to the device and
its served demand back: the program's `elastic.h2d` and `elastic.d2h`
spans (`repro.obs`). Not listed in `BENCHMARK.json`: a listed metric
that reads nothing fails a traced run, and the cell is also traced on
programs from before these spans."""
from bench.spans import total_ms

LAYER = "elasticity transfers"
UNIT = "ms"
MOVES = "container_epochs_per_s"
REQUIRED = ("elastic.h2d", "elastic.d2h")


def read(ctx):
    return total_ms(ctx.trace, REQUIRED)
