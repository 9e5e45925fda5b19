"""The comparison that decides `correct`.

The timed path's answers are the sweep's aggregate rows, one per (target,
policy), and the region plan the sweep computed. Every sweep of the window
must return the warm-up sweep's rows. Once the window has closed, the
cell's reference (`bench/ref/<reference>.py`, found through its mix)
recomputes the rows of a sample of the targets, drawn from the seed, and
the plan where the mix has one: each sampled row is compared key by key
(the reference's `COUNT_KEYS` exactly, every other number by its relative
gap), the window's last plan assignment by assignment, and the warm-up's
and the window's last plan are held to each region's capacity in every
epoch.
"""
from __future__ import annotations

import numpy as np

from bench.ref.placed import COUNT_KEYS


def sampled_targets(targets, k: int, seed: int) -> list:
    """`k` of the cell's targets, drawn from the seed, in sweep order."""
    rng = np.random.default_rng([seed, 0x7a29])
    idx = sorted(rng.choice(len(targets), size=min(k, len(targets)),
                            replace=False).tolist())
    return [targets[i] for i in idx]


def _flat(row: dict) -> dict:
    """A row's numbers, with the time-on-slice fractions as their own keys."""
    out = {k: v for k, v in row.items()
           if k not in ("policy", "target", "time_on_slice")}
    for k, v in row.get("time_on_slice", {}).items():
        out[f"time_on_slice.{k}"] = v
    return out


def rows_gap(got: list, ref: list, count_keys=COUNT_KEYS) -> dict:
    """Compare rows matched by (policy, target): the worst relative gap
    |a - b| / max(|b|, 1) over every number, the number of `count_keys`
    that differ, and the number of rows or keys present on one side
    only."""
    gap, counts, missing = 0.0, 0, 0
    by_key = {(r["policy"], float(r["target"])): r for r in got}
    worst = None
    for rr in ref:
        rg = by_key.get((rr["policy"], float(rr["target"])))
        if rg is None:
            missing += 1
            continue
        a, b = _flat(rg), _flat(rr)
        for k in set(a) | set(b):
            # a time-on-slice share of 0 is left out of the row
            if k.startswith("time_on_slice."):
                a.setdefault(k, 0.0)
                b.setdefault(k, 0.0)
            if k not in a or k not in b:
                missing += 1
                continue
            x, y = float(a[k]), float(b[k])
            if not (np.isfinite(x) and np.isfinite(y)):
                missing += 0 if x == y else 1
                continue
            if k in count_keys and x != y:
                counts += 1
            g = abs(x - y) / max(abs(y), 1.0)
            if g > gap:
                gap, worst = g, k
    return {"row_rel_gap": gap, "count_mismatches": counts,
            "missing": missing, "worst_key": worst}


def over_capacity_epochs(assign: np.ndarray, n_regions: int,
                         cap: int) -> int:
    """Epochs in which some region holds more containers than `cap`."""
    over = np.zeros(assign.shape[0], dtype=bool)
    for r in range(n_regions):
        over |= (assign == r).sum(axis=1) > cap
    return int(over.sum())


def plan_mismatches(got: np.ndarray, ref: np.ndarray) -> int:
    """(epoch, container) assignments that differ; all of them when the
    shapes differ."""
    if got is None or got.shape != ref.shape:
        return int(ref.size)
    return int(np.count_nonzero(got != ref))


def judge(warm: list, sweeps: list, ref: list, limits: dict,
          window_compiles: int, plans: dict, count_keys=COUNT_KEYS) -> dict:
    """Every number compared, each with its limit, and the verdict.

    `plans` holds `plan_mismatches` and `over_capacity_epochs`, read from
    the timed path's plans, or is None where the reference has no plan.
    `failed` counts the window's sweeps whose rows differ from the warm-up
    sweep's rows."""
    failed = sum(1 for rows in sweeps if rows != warm)
    cmp = rows_gap(sweeps[0] if sweeps else [], ref, count_keys)
    checks = {
        "row_rel_gap": (cmp["row_rel_gap"], limits["row_rel_gap"]),
        "count_mismatches": (cmp["count_mismatches"],
                             limits["count_mismatches"]),
    }
    if plans is not None:
        checks.update({k: (plans[k], limits[k]) for k in (
            "plan_mismatches", "over_capacity_epochs")})
    checks.update({
        "missing_keys": (cmp["missing"], 0),
        "failed_sweeps": (failed, 0),
        "window_compiles": (window_compiles, 0),
    })
    correct = bool(sweeps) and all(v <= lim for v, lim in checks.values())
    return {"correct": correct, "failed": failed, "checks": checks,
            "worst_key": cmp["worst_key"]}
