"""Host time spent preparing the layers' inputs: the program's
`sweep.faults` (the observed feed and the meter gaps), `sweep.traffic`,
`sweep.energy`, `sweep.elastic_budget` and `elastic.prepare` (the
elasticity forecasts and budgets) spans (`repro.obs`). Reads nothing
where one of them is missing from the traced sweep. Not listed in
`BENCHMARK.json`: a listed metric that reads nothing fails a traced run,
and the cell is also traced on programs from before these spans."""
from bench.spans import total_ms

LAYER = "layer preparation"
UNIT = "ms"
MOVES = "container_epochs_per_s"
REQUIRED = ("sweep.faults", "sweep.traffic", "sweep.energy",
            "sweep.elastic_budget", "elastic.prepare")


def read(ctx):
    return total_ms(ctx.trace, REQUIRED)
