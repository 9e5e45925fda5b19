"""Bytes the sweep pushed to the device, in GB, from the program's counter
(`repro.obs.last_sweep()`, of the last sweep run: the traced one): what
the `plan.h2d` and `fleet.h2d` spans move, and so what `h2d_ms` waits
for."""

LAYER = "host to device transfer"
UNIT = "GB"
MOVES = "container_epochs_per_s"


def read(ctx):
    from repro.obs import last_sweep
    n = last_sweep().get("h2d_bytes", 0)
    return n / 1e9 if n else None
