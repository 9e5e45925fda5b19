"""Benchmark harness: one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus writes full row data to
benchmarks/out/ as CSV for plotting). Run:

    PYTHONPATH=src python -m benchmarks.run \
        [--only fleet_sweep,fleet_sweep_jax] [--fast true] [--json out.json]

``--only`` takes a comma-separated entry list; ``--json`` additionally
writes per-entry ``{us_per_call, wall_s, warmup_s, steady_s,
peak_rss_mb, derived}`` to the given path (the CI benchmark-regression
gate feeds this to benchmarks.check_regression). ``wall_s`` is the
entry's total wall-clock; entries that jit-compile (the ``*_jax`` ones)
report ``warmup_s`` (first call, includes compile) and ``steady_s``
(best steady-state call) separately, and their ``speedup_x`` metrics
are computed from steady state only — so jit compile time never
pollutes regression floors. ``peak_rss_mb`` is the process peak-RSS
high-water mark at entry end; memory gates (the jax-sweep target's
ceiling) run their entry with ``--only`` in a fresh process so the mark
is theirs alone.
"""
from __future__ import annotations

import csv
import json
import os
import resource
import sys
import time


def _peak_rss_mb() -> float:
    """Process peak RSS in MB (ru_maxrss is KB on Linux, bytes on
    macOS). A high-water mark: per-entry values are cumulative across
    the run, so memory gates should run their entry with ``--only`` in
    a fresh process (the Makefile's jax-sweep target does)."""
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform == "darwin":                       # pragma: no cover
        return rss / 1e6
    return rss / 1024.0

def _setup_jax():
    """CPU-tuned XLA flags for the jax-backend entries (the shared
    helper appends them only when absent, so explicit user settings
    win; they must be set before the first jax backend initialization)
    and the persistent compilation cache, so a warm run's ``warmup_s``
    is mostly cache reads."""
    from repro.compile_cache import enable_compile_cache
    from repro.core.fleet_jax import ensure_cpu_xla_flags
    ensure_cpu_xla_flags()
    enable_compile_cache()


def _rows_to_csv(name: str, rows: list):
    if not rows:
        return
    out_dir = os.path.join(os.path.dirname(__file__), "out")
    os.makedirs(out_dir, exist_ok=True)
    keys = list(rows[0].keys())
    with open(os.path.join(out_dir, f"{name}.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, keys, extrasaction="ignore")
        w.writeheader()
        for r in rows:
            w.writerow(r)


def main() -> None:
    _setup_jax()
    args = {}
    argv = sys.argv[1:]
    for i in range(0, len(argv) - 1, 2):
        args[argv[i].lstrip("-")] = argv[i + 1]
    fast = args.get("fast", "false") == "true"

    from benchmarks import figs
    n_small = 10 if fast else 40
    entries = [
        ("fig1_regions", figs.fig1_regions, {}),
        ("fig2_traces", figs.fig2_traces, {}),
        ("fig3_workload", figs.fig3_workload, {"n_vms": 60 if fast else 300}),
        ("fig6_power", figs.fig6_power, {}),
        ("fig7_migration", figs.fig7_migration, {}),
        ("fig10_prototype", figs.fig10_prototype, {}),
        ("fig11_12_highvar", figs.fig11_12_highvar, {"n_jobs": n_small}),
        ("fig13_14_medvar", figs.fig13_14_medvar, {"n_jobs": n_small}),
        ("fig15_16_variants", figs.fig15_16_variants, {"n_jobs": max(n_small // 2, 6)}),
        ("fig17_server_time", figs.fig17_server_time, {"n_jobs": max(n_small // 2, 6)}),
        # vectorized fleet simulator vs looped simulate() (64x4x3 sweep);
        # fast mode shortens the traces, not the sweep shape
        ("fleet_sweep", figs.fleet_sweep, {"days": 2 if fast else 3}),
        # multi-region placement planner, scalar reference vs (N, R) batch
        ("placement_sweep", figs.placement_sweep,
         {"days": 2 if fast else 3}),
        # jit/scan JAX backend vs the NumPy fleet/placement kernels at
        # N >= 5000 containers (steady state vs compile split)
        ("fleet_sweep_jax", figs.fleet_sweep_jax,
         {"days": 2 if fast else 3}),
        ("placement_sweep_jax", figs.placement_sweep_jax,
         {"days": 2 if fast else 3}),
        # pallas admission kernel (interpret on CPU) parity + floor
        ("placement_sweep_pallas", figs.placement_sweep_pallas,
         {"n_containers": 256 if fast else 384, "days": 2}),
        # the N=1M placed sweep (fast mode: same path, 6k containers)
        ("jax_sweep_scale", figs.jax_sweep_scale,
         {"n_traces": 1500, "n_targets": 4} if fast
         else {"n_traces": 100_000, "n_targets": 10}),
        # carbon-aware traffic: 1M-user routing + autoscaling, carbon
        # vs latency routing, fleet-vs-jax sweep-with-traffic parity
        ("traffic_sweep", figs.traffic_sweep, {"n_users": 1_000_000}),
        # per-container elasticity: (N, K) greedy speedup + 3-backend
        # parity, shaped-budget oracle/forecast/persistence ablation
        ("elasticity_sweep", figs.elasticity_sweep,
         {"n_containers": 300, "days": 4} if fast
         else {"n_containers": 2000, "days": 10}),
        # virtual energy supply: overhead vs plain fleet sweep, supply
        # ledger invariants, fleet-vs-jax parity through SweepSpec
        ("energy_sweep", figs.energy_sweep,
         {"n_containers": 200, "days": 2} if fast
         else {"n_containers": 400, "days": 4}),
        # signal-plane fault injection: degradation-ladder overshoot vs
        # oracle/hold-forever, conservative zero-violation certificate,
        # fleet-vs-jax parity with the full fault plan enabled
        ("robustness_sweep", figs.robustness_sweep,
         {"n_traces": 48, "n_targets": 2} if fast
         else {"n_traces": 96, "n_targets": 3}),
    ]
    only = args.get("only")
    only_set = set(only.split(",")) if only else None
    if only_set:
        known = {name for name, _, _ in entries}
        unknown = only_set - known
        if unknown:
            raise SystemExit(f"unknown benchmark entries {sorted(unknown)}; "
                             f"known: {sorted(known)}")

    report = {}
    print("name,us_per_call,derived")
    for name, fn, kw in entries:
        if only_set and name not in only_set:
            continue
        t0 = time.perf_counter()
        rows, derived = fn(**kw)
        us = (time.perf_counter() - t0) * 1e6
        _rows_to_csv(name, rows)
        report[name] = {
            "us_per_call": us,
            "wall_s": us / 1e6,
            "warmup_s": derived.get("warmup_s"),
            "steady_s": derived.get("steady_s"),
            "peak_rss_mb": _peak_rss_mb(),
            "derived": derived,
        }
        compact = json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                              for k, v in derived.items()}, default=str)
        print(f"{name},{us:.0f},{compact}")
    if "json" in args:
        out_path = args["json"]
        d = os.path.dirname(out_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2, default=str)


if __name__ == "__main__":
    main()
