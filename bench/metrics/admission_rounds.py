"""Preference rounds the region plan's capacity admission ran per epoch,
from the program's counter (`repro.obs.last_sweep()`, of the last sweep
run: the traced one). Each round is one pass of the admission kernel;
at R regions an epoch runs 1 to R of them."""

LAYER = "placement kernel"
UNIT = "rounds/ep"
MOVES = "container_epochs_per_s"


def read(ctx):
    from repro.obs import last_sweep
    rounds = last_sweep().get("admission_rounds", 0)
    return rounds / ctx.dims["T"] if rounds else None
