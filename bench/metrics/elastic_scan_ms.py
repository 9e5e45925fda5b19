"""Device time of the elasticity scan: the `_elastic_scan` program
(core/elasticity_jax.py), matched in the trace's program executions."""
from bench.trace import matching, total_s

LAYER = "elasticity"
UNIT = "ms"
MOVES = "container_epochs_per_s"
MATCH = "_elastic_scan"


def read(ctx):
    ev = matching(ctx.trace.modules, MATCH)
    return total_s(ev) * 1e3 / ctx.trace.chips if ev else None
