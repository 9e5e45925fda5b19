"""Device time of the fleet scan: the `_fleet_scan` program
(core/fleet_jax.py), matched in the trace's program executions."""
from bench.trace import matching, total_s

LAYER = "fleet scan"
UNIT = "ms"
MOVES = "container_epochs_per_s"
MATCH = "_fleet_scan"


def read(ctx):
    ev = matching(ctx.trace.modules, MATCH)
    return total_s(ev) * 1e3 / ctx.trace.chips if ev else None
