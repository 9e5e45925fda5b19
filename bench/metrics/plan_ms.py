"""Device time of the region plan: the `_plan_scan` program
(cluster/placement_jax.py), matched in the trace's program executions."""
from bench.trace import matching, total_s

LAYER = "placement"
UNIT = "ms"
MOVES = "container_epochs_per_s"
MATCH = "_plan_scan"


def read(ctx):
    ev = matching(ctx.trace.modules, MATCH)
    return total_s(ev) * 1e3 / ctx.trace.chips if ev else None
