"""Bytes the sweep pulled from the device, in GB, from the program's
counter (`repro.obs.last_sweep()`, of the last sweep run: the traced
one): what the `plan.d2h` and `fleet.d2h` spans move, and so what
`d2h_ms` waits for."""

LAYER = "device to host transfer"
UNIT = "GB"
MOVES = "container_epochs_per_s"


def read(ctx):
    from repro.obs import last_sweep
    n = last_sweep().get("d2h_bytes", 0)
    return n / 1e9 if n else None
