"""Share of the HBM roofline reached by the Pallas admission kernel
`_round_kernel` (cluster/placement_pallas.py).

The kernel's Python name does not reach the trace: its events are the
device operations with `custom_call_target="tpu_custom_call"` that run
inside the `_plan_scan` program, the one Pallas call of the region plan.

One admission round reads the (R, N) preference ranks and the (N,) current
region, eligibility, destination and strike mask, and writes the (N,)
destination and strike mask, all int32: 4 * N * (R + 6) bytes over the
round's real N (the traces) and R, whatever tile padding an implementation
adds. The round does no arithmetic worth counting against the compute peak,
so its bound is the bytes over the HBM bandwidth.
"""
from bench.trace import inside, matching, total_s

LAYER = "placement kernel"
UNIT = "%"
MOVES = "container_epochs_per_s"
MATCH = 'custom_call_target="tpu_custom_call"'
PROGRAM = "_plan_scan"


def round_bytes(n: int, r: int) -> int:
    """Bytes one admission round must move for N containers over R regions."""
    return 4 * n * (r + 6)


def read(ctx):
    ev = inside(matching(ctx.trace.ops, MATCH),
                matching(ctx.trace.modules, PROGRAM))
    t = total_s(ev)
    if not ev or t <= 0.0:
        return None
    need_s = (len(ev) * round_bytes(ctx.dims["n_traces"], ctx.dims["R"])
              / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / t
