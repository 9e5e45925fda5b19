"""Host time spent preparing the sweep's inputs: the self time of the
program's `sweep.prepare` span (stacking the traces, less the region plan
it holds), the plan's and the fleet scan's own input preparation
(`plan.prepare`, `fleet.prepare`), and the traffic, energy and elastic
budget prologues where a cell runs those layers (`repro.obs`)."""
from bench.spans import self_ms, total_ms

LAYER = "host preparation"
UNIT = "ms"
MOVES = "container_epochs_per_s"
REQUIRED = ("fleet.prepare",)
OPTIONAL = ("plan.prepare", "sweep.traffic", "sweep.energy",
            "sweep.elastic_budget")


def read(ctx):
    own = self_ms(ctx.trace, "sweep.prepare", "plan")
    rest = total_ms(ctx.trace, REQUIRED, OPTIONAL)
    if own is None or rest is None:
        return None
    return own + rest
