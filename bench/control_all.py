"""Readings of the comparison that decides `correct` for the all-layer
reference (`bench/ref/all.py`), per seed: its control.

The control is the reference put in the program's place with one stated
guarantee broken: every region admits 1% more containers than its
capacity, as in the placed cell's control (`bench/control.py`). Capacity
binds here too, so the plan changes and with it every layer downstream,
and the comparison has to call it wrong. The control's readings are the
upper readings the limits in the configuration file were set below.

The other reading, `f32`, puts the reference on its inputs (traces,
region carbon) rounded to float32 in the program's place: the least that
computing in the precision below the configuration's float64 changes.
`row_rel_gap`'s limit lies below this reading.

    python3 -m bench.control_all --workload r3_all --seeds 11 12 \
        [--modes capacity f32]

It needs no accelerator.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import numpy as np

from bench import cells, check
from bench.gen.fleet import make_inputs
from bench.ref import all as ref
from bench.ref import placed

SLACK = 0.01


MODES = ("capacity", "f32")


def readings(bench: dict, name: str, seed: int, modes=("capacity",),
             sizes: dict = None) -> dict:
    """The comparison's numbers at one seed, by mode: the control
    ("capacity") and the reference on float32 inputs ("f32")."""
    cell = cells.workload(bench, name)
    cfg = {**cells.config(bench, cell["config"]), **(sizes or {})}
    mix = cells.mix(cell["traffic"])
    inputs = make_inputs(cfg, seed, mix)
    targets = check.sampled_targets(inputs["targets"], mix["check_targets"],
                                    seed)
    rows, plan = ref.sweep(cfg, inputs, targets)
    R, cap = len(cfg["regions"]), placed.capacity(cfg)
    out = {}
    for mode in modes:
        if mode == "f32":
            low = {k: inputs[k].astype(np.float32).astype(np.float64)
                   for k in ("traces", "regions")}
            ctl, ctl_plan = ref.sweep(cfg, {**inputs, **low}, targets)
        elif mode == "capacity":
            ctl, ctl_plan = ref.layers_sweep(
                cfg, inputs, targets, cap=int(math.ceil(cap * (1 + SLACK))))
        else:
            raise ValueError(f"unknown mode {mode!r}; known: {MODES}")
        plans = {"plan_mismatches": check.plan_mismatches(
                     ctl_plan["assign"], plan["assign"]),
                 "over_capacity_epochs": check.over_capacity_epochs(
                     ctl_plan["assign"], R, cap)}
        v = check.judge(ctl, [ctl], rows, cfg["limits"], 0, plans,
                        ref.COUNT_KEYS)
        out[mode] = {"correct": v["correct"], "worst_key": v["worst_key"],
                     **{k: val for k, (val, _) in v["checks"].items()}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="+", choices=MODES,
                    default=["capacity"])
    args = ap.parse_args(argv)
    bench = cells.load()
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(bench, args.workload, seed, args.modes)
        print(json.dumps({"workload": args.workload, "seed": seed, **r,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
