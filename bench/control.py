"""Readings of the comparison that decides `correct`, per seed: its control
and, with `--program`, the program itself.

The control is the reference put in the program's place with one
guarantee of the configuration broken: every region admits 1% more
containers than its capacity. Capacity binds in these deployments, so a
planner that is a little loose about admission changes the plan, and the
comparison has to call it wrong. The control's readings are the upper
readings the limits in the configuration file were set below; the
program's (one sweep of the timed path per seed, at the cell's own size)
are the lower ones.

    python3 -m bench.control --workload r3_placed --seeds 11 12 13
    python3 -m bench.control --workload r3_placed --seeds 11 12 --program

The control needs no accelerator; `--program` runs the timed path on the
device JAX finds.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

from bench import cells, check, sweep
from bench.gen.fleet import make_inputs
from bench.ref import placed

SLACK = 0.01


def _verdict(cfg: dict, rows: list, assigns: list, ref: list,
             ref_plan: dict) -> dict:
    R, cap = len(cfg["regions"]), placed.capacity(cfg)
    plans = {"plan_mismatches": sum(check.plan_mismatches(a, ref_plan["assign"])
                                    for a in assigns),
             "over_capacity_epochs": sum(check.over_capacity_epochs(a, R, cap)
                                         for a in assigns)}
    v = check.judge(rows, [rows], ref, cfg["limits"], 0, plans)
    return {"correct": v["correct"], "worst_key": v["worst_key"],
            **{k: val for k, (val, _) in v["checks"].items()}}


def reading(bench: dict, name: str, seed: int, sizes: dict = None,
            program: bool = False) -> dict:
    """The comparison's numbers for the control (and the program) at one
    seed."""
    cell = cells.workload(bench, name)
    cfg = {**cells.config(bench, cell["config"]), **(sizes or {})}
    mix = cells.mix(cell["traffic"])
    inputs = make_inputs(cfg, seed)
    targets = check.sampled_targets(inputs["targets"], mix["check_targets"],
                                    seed)
    ref, ref_plan = placed.sweep(cfg, inputs, targets)
    loose = int(math.ceil(placed.capacity(cfg) * (1 + SLACK)))
    ctl, ctl_plan = placed.sweep(cfg, inputs, targets, cap=loose)
    out = {"workload": name, "seed": seed,
           "control": _verdict(cfg, ctl, [ctl_plan["assign"]], ref,
                               ref_plan)}
    if program:
        spec = sweep.program_sweep(cfg, mix, inputs)
        with sweep.PlanTap() as tap:
            rows = spec.run().rows
        out["program"] = _verdict(cfg, rows, [tap.last.assign], ref,
                                  ref_plan)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    bench = cells.load()
    if args.program:
        sys.path.insert(0, str(cells.ROOT / "src"))
        import jax
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        d0 = jax.devices()[0]
        print(f"device: platform={d0.platform} device_kind={d0.device_kind}",
              file=sys.stderr, flush=True)
    for seed in args.seeds:
        t = time.perf_counter()
        r = reading(bench, args.workload, seed, program=args.program)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
