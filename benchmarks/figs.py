"""Benchmark implementations: one function per paper table/figure.

Each returns (rows, derived) where rows are CSV-able dicts and `derived`
is the headline number validated against the paper's claim.
"""
from __future__ import annotations

import time

import numpy as np


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


# ---------------------------------------------------------------------------
# Fig 1: 27-region average carbon-intensity + CoV, tier structure
# ---------------------------------------------------------------------------

def fig1_regions():
    from repro.carbon.regions import REGIONS, tier_means, tier_of
    rows = [{"region": r.name, "avg_g_kwh": r.avg, "cov": r.cov,
             "tier": tier_of(r.cov)}
            for r in sorted(REGIONS.values(), key=lambda x: x.cov)]
    means = tier_means()
    avgs = [r.avg for r in REGIONS.values()]
    derived = {
        "n_regions": len(rows),
        "spread_x": max(avgs) / min(avgs),                  # paper: >500x
        "frac_low_cov": np.mean([r.cov < 0.05 for r in REGIONS.values()]),
        "tier_mean_low": means["low"],                      # paper: 551
        "tier_mean_mid": means["mid"],                      # paper: 344
        "tier_mean_high": means["high"],                    # paper: 189
    }
    return rows, derived


# ---------------------------------------------------------------------------
# Fig 2: representative region traces (low/mid/high CoV over 96 h)
# ---------------------------------------------------------------------------

def fig2_traces():
    from repro.carbon.traces import synth_trace, trace_cov
    from repro.carbon.regions import REGIONS
    rows = []
    derived = {}
    for name in ("PL", "NL", "CAISO"):
        tr = synth_trace(name, hours=96, seed=0)
        for h, v in enumerate(tr):
            rows.append({"region": name, "hour": h, "g_kwh": float(v)})
        derived[f"{name}_cov"] = trace_cov(synth_trace(name, hours=24 * 365))
        derived[f"{name}_target_cov"] = REGIONS[name].cov
    return rows, derived


# ---------------------------------------------------------------------------
# Fig 3: Azure-like VM population CoV mixture
# ---------------------------------------------------------------------------

def fig3_workload(n_vms: int = 300):
    from repro.workload.azure_like import population_stats, sample_population
    pop = sample_population(n_vms, days=3, seed=0)
    stats = population_stats(pop)
    rows = [{"vm": i, "mean_util": t.mean, "cov": t.cov}
            for i, t in enumerate(pop)]
    # paper: 8% below 0.25, >50% above 0.4, 30% above 1.0, 43% mean<10%
    return rows, stats


# ---------------------------------------------------------------------------
# Fig 6: power-model linearity + calibration
# ---------------------------------------------------------------------------

def fig6_power():
    from repro.power.model import (LinearPowerModel, calibrate_linear,
                                   component_power_sweep)
    truth = LinearPowerModel(100.0, 200.0)
    sweep = component_power_sweep(truth, seed=0)
    model, r2 = calibrate_linear(sweep["util"], sweep["cpu"])
    rows = [{"util": u, **{c: sweep[c][i] for c in
                           ("cpu", "memory", "disk", "network")}}
            for i, u in enumerate(sweep["util"])]
    dyn_range = {c: max(sweep[c]) - min(sweep[c])
                 for c in ("cpu", "memory", "disk", "network")}
    return rows, {"fit_base_w": model.base_w, "fit_peak_w": model.peak_w,
                  "r2": r2, **{f"dyn_range_{k}": v for k, v in dyn_range.items()}}


# ---------------------------------------------------------------------------
# Fig 7: migration time vs state size — measured on our checkpoint path
# ---------------------------------------------------------------------------

def fig7_migration():
    import os
    os.environ.setdefault("XLA_FLAGS", "")
    import tempfile
    import jax
    from repro.train import checkpoint as CKPT

    rows = []
    sizes_mb = [8, 32, 128]
    times = []
    for mb in sizes_mb:
        n = mb * 1024 * 1024 // 4
        state = {"w": jax.numpy.arange(n, dtype=jax.numpy.float32)}
        with tempfile.TemporaryDirectory() as d:
            info = CKPT.save(d, state, step=0)
            t0 = time.perf_counter()
            CKPT.load(d, {"w": jax.ShapeDtypeStruct((n,), jax.numpy.float32)})
            restore_s = time.perf_counter() - t0
        rows.append({"state_mb": mb, "save_s": info["total_s"],
                     "restore_s": restore_s,
                     "total_s": info["total_s"] + restore_s})
        times.append(info["total_s"] + restore_s)
    # linearity check (paper: all curves linear in footprint)
    x = np.array(sizes_mb, dtype=float)
    y = np.array(times)
    slope, intercept = np.polyfit(x, y, 1)
    pred = slope * x + intercept
    r2 = 1 - np.sum((y - pred) ** 2) / max(np.sum((y - y.mean()) ** 2), 1e-12)
    # model-side numbers (paper's 7 GB < 2 min claim)
    from repro.cluster.migration import MigrationCostModel
    m = MigrationCostModel()
    return rows, {"linear_r2": r2, "s_per_gb_measured": slope * 1024,
                  "model_7gb_stop_copy_s": m.stop_and_copy_time(7.0)}


# ---------------------------------------------------------------------------
# Fig 10: prototype timeseries (single container, EE policy)
# ---------------------------------------------------------------------------

def fig10_prototype():
    from repro.carbon.intensity import ConstantProvider
    from repro.cluster.slices import paper_family
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig, simulate

    fam = paper_family()
    # ~1 h at 1-min intervals; carbon steady (as in the paper's Fig 10 run)
    t = np.arange(60)
    demand = 0.45 + 0.25 * np.sin(2 * np.pi * t / 40.0) * (t > 10)
    cfg = SimConfig(target_rate=45.0, interval_s=60.0, record_series=True,
                    state_gb=0.5)
    res = simulate(CarbonContainerPolicy(variant="energy"), fam, demand,
                   ConstantProvider(300.91), cfg)
    s = res.series
    rows = [{"t_min": s["t"][i] / 60.0, "carbon_rate": s["carbon_rate"][i],
             "slice": str(s["slice"][i]), "duty": s["duty"][i],
             "util": s["util"][i], "demand": s["demand"][i]}
            for i in range(len(s["t"]))]
    return rows, {"avg_rate": res.avg_carbon_rate, "target": 45.0,
                  "migrations": res.migrations,
                  "under_target": res.avg_carbon_rate <= 45.0}


# ---------------------------------------------------------------------------
# Figs 11-14: policy comparison across targets (high / medium variability)
# ---------------------------------------------------------------------------

def _policy_sweep(region: str, n_jobs: int, targets, days=7,
                  backend="fleet"):
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.slices import paper_family
    from repro.core.policy import (CarbonAgnosticPolicy,
                                   CarbonContainerPolicy,
                                   SuspendResumePolicy, VScaleOnlyPolicy)
    from repro.core.simulator import SimConfig, sweep_population
    from repro.workload.azure_like import sample_population

    fam = paper_family()
    carbon = TraceProvider.for_region(region, hours=24 * days, seed=1)
    traces = [t.util for t in sample_population(n_jobs, days=days, seed=2)]
    policies = {
        "carbon_agnostic": CarbonAgnosticPolicy,
        "suspend_resume": SuspendResumePolicy,
        "vscale_only": lambda: VScaleOnlyPolicy(),
        "carbon_containers": lambda: CarbonContainerPolicy(variant="energy"),
    }
    rows = sweep_population(policies, fam, traces, carbon, targets,
                            SimConfig(target_rate=0.0), backend=backend)
    return rows


def fig11_12_highvar(n_jobs: int = 40):
    targets = [20.0, 35.0, 50.0, 65.0, 80.0]
    rows = _policy_sweep("CAISO", n_jobs, targets)
    cc = [r for r in rows if r["policy"] == "carbon_containers"]
    sr = [r for r in rows if r["policy"] == "suspend_resume"]
    derived = {
        "cc_all_under_target": all(r["carbon_rate_mean"] <= r["target"] for r in cc),
        "cc_throttle_mean": np.mean([r["throttle_mean"] for r in cc]),
        "sr_throttle_mean": np.mean([r["throttle_mean"] for r in sr]),
        "cc_beats_sr_throttle": all(
            c["throttle_mean"] <= s["throttle_mean"] + 0.1
            for c, s in zip(cc, sr)),
    }
    return rows, derived


def fig13_14_medvar(n_jobs: int = 40):
    targets = [20.0, 35.0, 50.0, 65.0, 80.0]
    rows = _policy_sweep("NL", n_jobs, targets)
    cc = [r for r in rows if r["policy"] == "carbon_containers"]
    vs = [r for r in rows if r["policy"] == "vscale_only"]
    derived = {
        "cc_all_under_target": all(r["carbon_rate_mean"] <= r["target"] for r in cc),
        "cc_vs_vscale_throttle": [
            (c["target"], c["throttle_mean"], v["throttle_mean"])
            for c, v in zip(cc, vs)],
        "cc_beats_vscale": all(
            c["throttle_mean"] <= v["throttle_mean"] + 0.5 for c, v in zip(cc, vs)),
    }
    return rows, derived


# ---------------------------------------------------------------------------
# Figs 15-17: energy-efficiency vs performance variants + slice residency
# ---------------------------------------------------------------------------

def fig15_16_variants(n_jobs: int = 30):
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.slices import paper_family
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig, sweep_population
    from repro.workload.azure_like import sample_population

    fam = paper_family()
    targets = [25.0, 45.0, 65.0, 85.0]
    out_rows = []
    derived = {}
    for region in ("CAISO", "NL"):
        carbon = TraceProvider.for_region(region, hours=24 * 7, seed=1)
        traces = [t.util for t in sample_population(n_jobs, days=7, seed=2)]
        rows = sweep_population(
            {"energy": lambda: CarbonContainerPolicy(variant="energy"),
             "performance": lambda: CarbonContainerPolicy(variant="performance")},
            fam, traces, carbon, targets, SimConfig(target_rate=0.0),
            backend="fleet")
        for r in rows:
            r["region"] = region
        out_rows.extend(rows)
        en = [r for r in rows if r["policy"] == "energy"]
        pf = [r for r in rows if r["policy"] == "performance"]
        derived[f"{region}_perf_emits_more"] = all(
            p["carbon_rate_mean"] >= e["carbon_rate_mean"] - 1e-9
            for p, e in zip(pf, en))
        derived[f"{region}_both_under_target"] = all(
            r["carbon_rate_mean"] <= r["target"] * 1.02 for r in rows)
    return out_rows, derived


# ---------------------------------------------------------------------------
# fleet_sweep: vectorized fleet simulator vs looped simulate() (perf record)
# ---------------------------------------------------------------------------

def _best_of_interleaved(fast_fn, slow_fn, rounds: int = 5,
                         fast_reps: int = 2):
    """Fair fast-vs-slow timing: interleave rounds so host load drift
    hits both sides alike, keep going until neither best-of improves
    (max `rounds`; the cheap vectorized side gets `fast_reps` per
    round). Returns (fast_out, fast_s, slow_out, slow_s)."""
    fast_s = slow_s = float("inf")
    fast_out = slow_out = None
    for _ in range(rounds):
        improved = False
        for _ in range(fast_reps):
            t0 = time.perf_counter()
            out = fast_fn()
            s = time.perf_counter() - t0
            if s < fast_s:
                fast_out, fast_s, improved = out, s, True
        t0 = time.perf_counter()
        out = slow_fn()
        s = time.perf_counter() - t0
        if s < slow_s:
            slow_out, slow_s, improved = out, s, True
        if not improved:
            break
    return fast_out, fast_s, slow_out, slow_s


def fleet_sweep(n_traces: int = 64, n_targets: int = 4, days: int = 3):
    """64-trace x 4-target x 3-policy sweep, scalar vs fleet backend.

    Headline numbers: `speedup_x` (wall-clock, best-of-N each) and
    `parity_max_abs_diff` (row-level agreement between backends; the fleet
    path is bit-compatible, so this is expected to be 0.0).

    Notes — `FleetSimulator._loop` temporary preallocation (PR 5): the
    `_LoopScratch` buffers took the CC-energy fleet run at T=576 from
    ~0.77s to ~0.70s at N=5040 (~6-8%) and were neutral at N=420
    (best-of-4, alternated A/B on an otherwise idle 2-vCPU host). NumPy's
    small-block cache already amortizes most temporary allocation: only
    single-pass ufunc-`out=` rewrites pay, `np.take(..., out=)` needs
    mode="clip" to match fancy indexing's fast path, and splitting a
    `np.where` into fill+masked-copy regressed ~8% and was reverted.
    """
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.slices import paper_family
    from repro.core.policy import (CarbonAgnosticPolicy,
                                   CarbonContainerPolicy,
                                   SuspendResumePolicy)
    from repro.core.simulator import SimConfig, sweep_population
    from repro.workload.azure_like import sample_population

    fam = paper_family()
    carbon = TraceProvider.for_region("CAISO", hours=24 * days, seed=1)
    traces = [t.util for t in sample_population(n_traces, days=days, seed=2)]
    targets = list(np.linspace(20.0, 80.0, n_targets))
    policies = {
        "carbon_agnostic": CarbonAgnosticPolicy,
        "suspend_resume": SuspendResumePolicy,
        "carbon_containers": lambda: CarbonContainerPolicy(variant="energy"),
    }
    cfg = SimConfig(target_rate=0.0)

    def _backend(backend):
        return lambda: sweep_population(policies, fam, traces, carbon,
                                        targets, cfg, backend=backend)

    rows_fleet, fleet_s, rows_scalar, scalar_s = _best_of_interleaved(
        _backend("fleet"), _backend("scalar"))
    keys = ("carbon_rate_mean", "carbon_rate_std", "throttle_mean",
            "throttle_std", "migrations_mean", "suspended_frac_mean")
    parity = max(abs(a[k] - b[k])
                 for a, b in zip(rows_scalar, rows_fleet) for k in keys)
    rows = [{"backend": "scalar", "wall_s": scalar_s, **{
             k: r[k] for k in ("policy", "target") + keys}}
            for r in rows_scalar]
    rows += [{"backend": "fleet", "wall_s": fleet_s, **{
              k: r[k] for k in ("policy", "target") + keys}}
             for r in rows_fleet]
    n_sims = n_traces * n_targets * len(policies)
    derived = {
        "n_sims": n_sims,
        "n_intervals": n_sims * len(traces[0]),
        "scalar_s": scalar_s,
        "fleet_s": fleet_s,
        "speedup_x": scalar_s / fleet_s,
        "parity_max_abs_diff": parity,
        "speedup_ge_20x": scalar_s / fleet_s >= 20.0,
    }
    return rows, derived


# ---------------------------------------------------------------------------
# placement_sweep: multi-region placement planner, scalar vs batch (perf
# record) + carbon saving of the placed fleet over the static baseline
# ---------------------------------------------------------------------------

def placement_sweep(n_containers: int = 192, days: int = 3):
    """Scalar greedy reference vs vectorized (N, R) placement planner.

    Headline numbers: `speedup_x` (wall-clock, best-of interleaved reps),
    `parity_max_abs_diff` (overhead/downtime agreement; the batch kernel
    is bit-compatible so this is expected to be 0.0), `assign_equal`
    (epoch-by-epoch region assignments identical), and
    `saving_vs_static_pct` (fleet emissions saved vs the no-migration
    baseline, stop-and-copy overhead included).
    """
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.cluster.slices import paper_family
    from repro.core.policy import CarbonContainerPolicy
    from repro.workload.azure_like import sample_population

    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in regions]
    traces = [t.util for t in sample_population(n_containers, days=days,
                                                seed=2)]
    demand = np.stack(traces, axis=1)
    rng = np.random.default_rng(3)
    state_gb = rng.choice([0.25, 1.0, 4.0], size=n_containers)
    cap = int(np.ceil(0.6 * n_containers))
    eng = PlacementEngine(
        fam, provs, region_names=regions,
        config=PlacementConfig(capacity=cap, min_dwell=6, hysteresis=0.10))

    plan_v, vec_s, plan_s, scalar_s = _best_of_interleaved(
        lambda: eng.plan(demand, state_gb=state_gb),
        lambda: eng.plan_scalar(demand, state_gb=state_gb))

    assign_equal = bool((plan_v.assign == plan_s.assign).all())
    parity = max(float(np.abs(plan_v.overhead_g - plan_s.overhead_g).max()),
                 float(np.abs(plan_v.downtime_s - plan_s.downtime_s).max()),
                 float(np.abs(plan_v.migrations - plan_s.migrations).max()))
    occ = plan_v.occupancy()
    over_cap = int((occ > cap).sum())

    res = eng.run(CarbonContainerPolicy("energy"), demand, targets=45.0,
                  state_gb=state_gb, plan=plan_v, compare_static=True)

    rows = [{"backend": b, "wall_s": s, "n_containers": n_containers,
             "n_epochs": demand.shape[0], "migrations":
             int(p.migrations.sum()), "overhead_g":
             float(p.overhead_g.sum())}
            for b, s, p in (("scalar", scalar_s, plan_s),
                            ("batch", vec_s, plan_v))]
    derived = {
        "n_containers": n_containers,
        "n_epochs": demand.shape[0],
        "scalar_s": scalar_s,
        "vec_s": vec_s,
        "speedup_x": scalar_s / vec_s,
        "parity_max_abs_diff": parity,
        "assign_equal": assign_equal,
        "over_capacity_epochs": over_cap,
        "placement_migrations": int(plan_v.migrations.sum()),
        "saving_vs_static_pct": res.saving_vs_static_pct,
        **{f"occ_end_{name}": int(occ[-1, r])
           for r, name in enumerate(regions)},
    }
    return rows, derived


# ---------------------------------------------------------------------------
# fleet_sweep_jax / placement_sweep_jax: the jit/scan device-resident JAX
# backend vs the NumPy fleet/placement kernels (perf record; compile time
# is reported separately from steady state so regression floors never see
# it)
# ---------------------------------------------------------------------------

def _steady_vs_numpy(jax_fn, numpy_fn, reps: int = 8):
    """Warm the jax side once (timed: includes jit compile), then
    interleave steady-state reps against the NumPy side so host load
    drift hits both alike. Returns (jax_out, warmup_s, steady_s,
    numpy_out, numpy_s)."""
    t0 = time.perf_counter()
    jax_out = jax_fn()
    warmup_s = time.perf_counter() - t0
    steady_s = numpy_s = float("inf")
    numpy_out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        jax_out = jax_fn()
        steady_s = min(steady_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        numpy_out = numpy_fn()
        numpy_s = min(numpy_s, time.perf_counter() - t0)
    return jax_out, warmup_s, steady_s, numpy_out, numpy_s


def fleet_sweep_jax(n_traces: int = 420, n_targets: int = 12,
                    days: int = 3):
    """Carbon Containers (energy) sweep over n_traces x n_targets =
    5040 containers with mixed-region stacked carbon traces: NumPy fleet
    backend vs the jit/scan JAX backend (`sweep_population` both ways).

    Headline numbers: `speedup_x` = fleet_s / steady_s (steady state:
    best-of interleaved reps after the warmup call), `warmup_s` (first
    call, includes jit compile — reported separately so it never
    pollutes regression floors), and `parity_max_abs_diff` across all
    aggregate row metrics (ceiling 1e-6; the NumPy backend itself stays
    pinned to the scalar loop at 1e-9, anchoring the chain).

    Requires jax; the CPU-tuned XLA flags (legacy runtime + 4 host
    devices for container-sharding) are set by benchmarks/run.py before
    jax initializes.
    """
    import jax
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.slices import paper_family
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig, sweep_population
    from repro.workload.azure_like import sample_population

    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in regions]
    traces = [t.util for t in sample_population(n_traces, days=days,
                                                seed=2)]
    T = len(traces[0])
    tvec = np.arange(T) * 300.0
    region_mat = np.stack([p.intensity_series(tvec) for p in provs], axis=1)
    # container i lives in region i % R: a (T, n_traces) stacked-trace
    # matrix, tiled across the target axis like the demand matrix
    cmat_tr = region_mat[:, np.arange(n_traces) % len(regions)]
    carbon = np.tile(cmat_tr, (1, n_targets))
    targets = list(np.linspace(20.0, 80.0, n_targets))
    policies = {"carbon_containers":
                lambda: CarbonContainerPolicy(variant="energy")}
    cfg = SimConfig(target_rate=0.0)

    def _backend(backend):
        return lambda: sweep_population(policies, fam, traces, carbon,
                                        targets, cfg, backend=backend)

    rows_jax, warmup_s, steady_s, rows_fleet, fleet_s = _steady_vs_numpy(
        _backend("jax"), _backend("fleet"))
    keys = ("carbon_rate_mean", "carbon_rate_std", "throttle_mean",
            "throttle_std", "migrations_mean", "suspended_frac_mean")
    parity = max(abs(a[k] - b[k])
                 for a, b in zip(rows_fleet, rows_jax) for k in keys)
    rows = [{"backend": b, "wall_s": s, **{k: r[k]
             for k in ("policy", "target") + keys}}
            for b, s, rws in (("fleet", fleet_s, rows_fleet),
                              ("jax", steady_s, rows_jax))
            for r in rws]
    n_containers = n_traces * n_targets
    derived = {
        "n_containers": n_containers,
        "n_epochs": T,
        "n_devices": len(jax.devices()),
        "fleet_s": fleet_s,
        "warmup_s": warmup_s,
        "steady_s": steady_s,
        "speedup_x": fleet_s / steady_s,
        "parity_max_abs_diff": parity,
        "speedup_ge_5x": fleet_s / steady_s >= 5.0,
    }
    return rows, derived


def placement_sweep_jax(n_containers: int = 2000, days: int = 3):
    """Multi-region placement planner at fleet scale: NumPy (N, R) batch
    kernel vs the jit/scan JAX planner (`plan_jax`), heterogeneous state
    sizes, per-region capacity.

    Headline numbers: `speedup_x` = numpy_s / steady_s (compile time in
    `warmup_s`, reported separately), `assign_equal` (epoch-by-epoch
    region assignments identical), `parity_max_abs_diff` on
    overhead/downtime/migrations (ceiling 1e-6; the NumPy planner stays
    bit-compatible with the greedy scalar reference), and
    `over_capacity_epochs` (must be 0).
    """
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.cluster.placement_jax import plan_jax
    from repro.cluster.slices import paper_family
    from repro.workload.azure_like import sample_population

    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in regions]
    traces = [t.util for t in sample_population(n_containers, days=days,
                                                seed=2)]
    demand = np.stack(traces, axis=1)
    rng = np.random.default_rng(3)
    state_gb = rng.choice([0.25, 1.0, 4.0], size=n_containers)
    cap = int(np.ceil(0.6 * n_containers))
    eng = PlacementEngine(
        fam, provs, region_names=regions,
        config=PlacementConfig(capacity=cap, min_dwell=6, hysteresis=0.10))

    plan_j, warmup_s, steady_s, plan_np, numpy_s = _steady_vs_numpy(
        lambda: plan_jax(eng, demand, state_gb=state_gb),
        lambda: eng.plan(demand, state_gb=state_gb))

    assign_equal = bool((plan_j.assign == plan_np.assign).all())
    parity = max(float(np.abs(plan_j.overhead_g - plan_np.overhead_g).max()),
                 float(np.abs(plan_j.downtime_s - plan_np.downtime_s).max()),
                 float(np.abs(plan_j.migrations - plan_np.migrations).max()))
    occ = plan_j.occupancy()
    rows = [{"backend": b, "wall_s": s, "n_containers": n_containers,
             "n_epochs": demand.shape[0],
             "migrations": int(p.migrations.sum()),
             "overhead_g": float(p.overhead_g.sum())}
            for b, s, p in (("numpy", numpy_s, plan_np),
                            ("jax", steady_s, plan_j))]
    derived = {
        "n_containers": n_containers,
        "n_epochs": demand.shape[0],
        "numpy_s": numpy_s,
        "warmup_s": warmup_s,
        "steady_s": steady_s,
        "speedup_x": numpy_s / steady_s,
        "parity_max_abs_diff": parity,
        "assign_equal": assign_equal,
        "over_capacity_epochs": int((occ > cap).sum()),
    }
    return rows, derived


def placement_sweep_pallas(n_containers: int = 384, days: int = 2):
    """Pallas admission-kernel dispatch check: `plan_jax` with
    `admission_impl="pallas"` (interpret mode on CPU — the same kernel
    Mosaic compiles on a TPU) vs the NumPy planner, tight capacity so
    every epoch exercises the ranked-admission rounds.

    Headline numbers: `assign_equal` / `parity_max_abs_diff` /
    `over_capacity_epochs` (the parity chain, same ceilings as
    placement_sweep_jax) and `speedup_x` vs NumPy. The regression floor
    is interpret-safe (~0.05x): interpret mode runs the kernel through
    XLA op-by-op, so the floor gates "not pathologically slow /
    parity intact", not kernel throughput — that needs the real
    accelerator path.
    """
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.cluster.placement_jax import plan_jax
    from repro.cluster.slices import paper_family
    from repro.workload.azure_like import sample_population_matrix

    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in regions]
    demand = sample_population_matrix(n_containers, days=days, seed=2)
    rng = np.random.default_rng(3)
    state_gb = rng.choice([0.25, 1.0, 4.0], size=n_containers)
    cap = int(np.ceil(0.55 * n_containers))
    eng = PlacementEngine(
        fam, provs, region_names=regions,
        config=PlacementConfig(capacity=cap, min_dwell=6, hysteresis=0.10))

    plan_p, warmup_s, steady_s, plan_np, numpy_s = _steady_vs_numpy(
        lambda: plan_jax(eng, demand, state_gb=state_gb,
                         admission_impl="pallas"),
        lambda: eng.plan(demand, state_gb=state_gb), reps=3)

    assign_equal = bool((plan_p.assign == plan_np.assign).all())
    parity = max(float(np.abs(plan_p.overhead_g - plan_np.overhead_g).max()),
                 float(np.abs(plan_p.downtime_s - plan_np.downtime_s).max()),
                 float(np.abs(plan_p.migrations - plan_np.migrations).max()))
    occ = plan_p.occupancy()
    rows = [{"backend": b, "wall_s": s, "n_containers": n_containers,
             "n_epochs": demand.shape[0],
             "migrations": int(p.migrations.sum()),
             "overhead_g": float(p.overhead_g.sum())}
            for b, s, p in (("numpy", numpy_s, plan_np),
                            ("pallas", steady_s, plan_p))]
    derived = {
        "n_containers": n_containers,
        "n_epochs": demand.shape[0],
        "numpy_s": numpy_s,
        "warmup_s": warmup_s,
        "steady_s": steady_s,
        "speedup_x": numpy_s / steady_s,
        "parity_max_abs_diff": parity,
        "assign_equal": assign_equal,
        "over_capacity_epochs": int((occ > cap).sum()),
    }
    return rows, derived


def fleet_1m_spec(n_traces: int = 100_000, n_targets: int = 10,
                  days: int = 1, backend: str = "jax"):
    """The `make jax-sweep` configuration as a `SweepSpec`: n_traces
    Azure-like traces x n_targets carbon targets (N = 1,000,000 at the
    defaults), 5-minute epochs over `days`, R = 3 regions (PL, NL,
    CAISO), capacity-planned placement at 60% of the traces, a 1M-user
    traffic layer, K = 4 elasticity under a shaped budget, the energy
    supply with one outage and one carbon shock, and a signal-plane
    fault plan (20% carbon-feed dropouts plus a blackout, power-meter
    gaps, migration failures). Everything is generated from seeds."""
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.cluster.slices import paper_family
    from repro.core.elasticity import ElasticityConfig
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig
    from repro.core.spec import SweepSpec
    from repro.energy import EnergyConfig, GridEventConfig
    from repro.robustness import (CarbonFeedFaults, DegradeConfig,
                                  FaultPlan, MigrationFaults,
                                  PowerTelemetryFaults)
    from repro.traffic import TrafficConfig, UserPopulation
    from repro.traffic.autoscale import ReplicaConfig
    from repro.workload.azure_like import sample_population_matrix

    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in regions]
    demand = sample_population_matrix(n_traces, days=days, seed=2)
    cap = int(np.ceil(0.6 * n_traces))
    eng = PlacementEngine(
        fam, provs, region_names=regions,
        config=PlacementConfig(capacity=cap, min_dwell=6, hysteresis=0.10))
    traffic = TrafficConfig(
        population=UserPopulation(n_users=1_000_000, n_regions=3, seed=3),
        replicas=ReplicaConfig(max_replicas=8, max_step=4))
    # mildly-binding shaped budget: ~2.5 g/epoch per trace keeps the
    # (N*K,) greedy genuinely selective without starving the fleet
    elastic = ElasticityConfig(k_levels=4, unit_capacity=0.3,
                               budget_g_per_epoch=2.5 * n_traces,
                               forecast="forecast", shape_budget=True)
    T_ep = 288 * days
    energy = EnergyConfig(events=GridEventConfig(
        outages=((1, T_ep // 3, T_ep // 24),),
        shocks=((-1, T_ep // 2, T_ep // 12, 1.6),)))
    # non-trivial fault plan: the observed (T, R) feed and the (T,) gap
    # vector are the only extra arrays — nothing (T, N)
    flt = FaultPlan(
        carbon=CarbonFeedFaults(dropout_prob=0.2,
                                blackouts=((-1, T_ep // 3, T_ep // 12),)),
        power=PowerTelemetryFaults(gap_prob=0.05),
        migration=MigrationFaults(fail_prob=0.2, backoff_cap=8),
        degrade=DegradeConfig(mode="ladder", ttl_epochs=3),
        seed=11)
    return SweepSpec(
        policies={"carbon_containers":
                  lambda: CarbonContainerPolicy(variant="energy")},
        family=fam, traces=demand,
        targets=list(np.linspace(20.0, 80.0, n_targets)),
        sim=SimConfig(target_rate=0.0), backend=backend, placement=eng,
        traffic=traffic, elasticity=elastic, energy=energy, faults=flt)


def planner_view(spec):
    """The placement engine as the sweep's planner sees it: grid shocks
    applied to the TRUE feed first (physical), then the degrade ladder
    on top — the planner only ever sees the observed signal. Planning
    on it (with the spec's fault plan) reproduces the sweep's region
    plan, for invariant and parity checks."""
    import copy

    from repro.energy.supply import event_matrices
    from repro.robustness.degrade import observe_intensity
    eng = spec.placement
    T = np.asarray(spec.traces).shape[0]
    shock_mult, _ = event_matrices(spec.energy.events, T, eng.n_regions)
    view = copy.copy(eng)
    view.regions = observe_intensity(eng._region_matrix(T) * shock_mult,
                                     spec.faults, eng.interval_s).observed
    return view


def jax_sweep_scale(n_traces: int = 100_000, n_targets: int = 10,
                    days: int = 1):
    """The N=1M placed fleet sweep (`fleet_1m_spec`) through the full
    jax path — vectorized trace generation, the capacity-planned region
    schedule (`plan_jax`), and the memory-lean indexed-carbon fleet scan
    (compact demand + in-step target tiling; no (T, N) array on host or
    device) — with every layer on: the 1M-user traffic layer modulates
    every container's demand, the virtual energy supply runs the host
    supply ledger on the compact fleet (cap_frac applied on host, carbon
    billed at the delivered mix through the indexed (c_eff, codes)
    layout), the per-container elasticity layer runs its own
    compact-width scan (the (N·K,) marginal-allocation argsort per
    epoch) whose served demand feeds the fleet scan, and the
    signal-plane fault plan degrades every decision plane. The 4 GB RSS
    ceiling holds with all of it on, and the energy invariants
    (conservation, zero cap/SoC violations) gate alongside the
    throughput floor.

    Headline numbers: `container_epochs_per_s` = N * T / steady_s
    (steady state: second sweep call, jit cache warm), `warmup_s`
    (first call, includes compile AND the placement plan),
    `over_capacity_epochs` (the plan is recomputed once outside the
    timed region for the invariant check — plans are deterministic, so
    it is the same plan the sweep used). NumPy comparison is deliberately
    absent: the fleet backend needs the ~2.3 GB tiled matrices and tens
    of minutes at this N — parity is pinned at 50k by
    tests/test_placement_scale.py instead.
    """
    from repro.cluster.placement_jax import plan_jax

    t0 = time.perf_counter()
    spec = fleet_1m_spec(n_traces, n_targets, days)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    rows_w = spec.run().rows
    warmup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows_jax = spec.run().rows
    steady_s = time.perf_counter() - t0

    plan = plan_jax(planner_view(spec), spec.traces,
                    state_gb=spec.sim.state_gb, faults=spec.faults)
    cap = spec.placement.config.capacity
    occ = plan.occupancy()
    n_containers = n_traces * n_targets
    T = spec.traces.shape[0]
    rows = [{"backend": "jax", "wall_s": steady_s,
             "n_containers": n_containers, "n_epochs": T,
             **{k: r[k] for k in ("policy", "target", "carbon_rate_mean",
                                  "throttle_mean", "migrations_mean")}}
            for r in rows_jax]
    derived = {
        "n_containers": n_containers,
        "n_traces": n_traces,
        "n_targets": n_targets,
        "n_epochs": T,
        "gen_s": gen_s,
        "warmup_s": warmup_s,
        "steady_s": steady_s,
        "container_epochs_per_s": n_containers * T / steady_s,
        "placement_migrations": int(plan.migrations.sum()),
        "over_capacity_epochs": int((occ > cap).sum()),
        "rows_match_warmup": rows_jax == rows_w,
        "traffic_n_users": spec.traffic.population.n_users,
        "traffic_served": rows_jax[0]["traffic_served"],
        "traffic_violation_rate": rows_jax[0]["traffic_violation_rate"],
        "traffic_carbon_per_request_g":
            rows_jax[0]["traffic_carbon_per_request_g"],
        "elastic_served_frac": rows_jax[0]["elastic_served_frac"],
        "elastic_level_epochs": rows_jax[0]["elastic_level_epochs"],
        "elastic_cap_violations": rows_jax[0]["elastic_cap_violations"],
        "energy_conservation_max_err_w":
            rows_jax[0]["energy_conservation_max_err_w"],
        "energy_cap_violations": int(rows_jax[0]["energy_cap_violations"]),
        "energy_soc_violations": int(rows_jax[0]["energy_soc_violations"]),
        "energy_outage_epochs": int(rows_jax[0]["energy_outage_epochs"]),
        "energy_solar_frac": rows_jax[0]["energy_solar_frac"],
        "energy_unmet_frac": rows_jax[0]["energy_unmet_frac"],
        "fault_stale_frac": rows_jax[0]["fault_stale_frac"],
        "fault_prior_frac": rows_jax[0]["fault_prior_frac"],
        "fault_floor_frac": rows_jax[0]["fault_floor_frac"],
        "fault_failed_migrations_mean":
            rows_jax[0]["fault_failed_migrations_mean"],
        "fault_unmetered_g_mean": rows_jax[0]["fault_unmetered_g_mean"],
    }
    return rows, derived


def fig17_server_time(n_jobs: int = 30):
    rows, _ = fig15_16_variants(n_jobs)
    out = []
    for r in rows:
        if r["region"] != "CAISO":
            continue
        for sl, frac in sorted(r["time_on_slice"].items()):
            out.append({"policy": r["policy"], "target": r["target"],
                        "slice": sl, "frac": frac})
    big = {}
    for r in rows:
        if r["region"] != "CAISO":
            continue
        large = sum(v for k, v in r["time_on_slice"].items() if k in ("x2", "x4"))
        big.setdefault(r["policy"], []).append(large)
    derived = {"perf_more_time_on_large": float(np.mean(big.get("performance", [0])))
               >= float(np.mean(big.get("energy", [0])))}
    return out, derived


# ---------------------------------------------------------------------------
# Carbon-aware traffic subsystem: routing speedup, carbon-vs-latency
# headline, end-to-end sweep parity
# ---------------------------------------------------------------------------

def traffic_sweep(n_users: int = 1_000_000, days: int = 1,
                  n_traces: int = 16):
    """The traffic subsystem's benchmark-gate entry.

    Three claims in one scenario (a 1M-user population across three
    regions 8 time-zone-hours apart, so every pair is SLO-feasible at
    the 200 ms bound and both routing policies violate nothing):

      - `speedup_x` / `parity_max_abs_diff`: the vectorized router vs
        the pure-Python reference on the same (T, R) request tensor
        (expected bit-identical — both fold admission sums left to
        right).
      - `cpr_ratio`: carbon routing must beat latency routing on
        carbon-per-request at an equal (zero) SLO-violation rate
        (`viol_rate_delta`); `over_capacity_epochs` pins the router's
        capacity invariant.
      - `sweep_parity_max_abs_diff`: `sweep_population(..., traffic=)`
        through the fleet backend (NumPy demand modulation) vs the jax
        backend (routing + autoscaling folded into the fleet scan),
        including the traffic_* row metrics.
    """
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.cluster.slices import paper_family
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig, sweep_population
    from repro.traffic import (RoutingConfig, TrafficConfig, UserPopulation,
                               request_matrix, route, route_scalar,
                               simulate_traffic)
    from repro.traffic.autoscale import ReplicaConfig
    from repro.workload.azure_like import sample_population

    T = 288 * days
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in regions]
    epochs_s = np.arange(T) * 300.0
    intensity = np.stack([p.intensity_series(epochs_s) for p in provs],
                         axis=1)
    pop = UserPopulation(n_users=n_users, n_regions=3,
                         tz_offset_h=(0.0, 8.0, 16.0), seed=3)
    reps = ReplicaConfig(throughput_rps=100.0, max_replicas=8, max_step=4)
    slo_ms = 200.0                  # all pairs at 140 ms: zero violations

    t0 = time.perf_counter()
    arr = request_matrix(pop, T, 300.0)
    gen_s = time.perf_counter() - t0
    cap = reps.max_capacity(300.0)
    lat = TrafficConfig(population=pop).latency_matrix()
    rcfg = RoutingConfig(slo_ms=slo_ms, policy="carbon")

    rt_vec, vec_s, rt_scl, scl_s = _best_of_interleaved(
        lambda: route(arr.requests, cap, intensity, lat, rcfg),
        lambda: route_scalar(arr.requests, cap, intensity, lat, rcfg),
        rounds=3)
    parity = max(float(np.max(np.abs(getattr(rt_vec, f)
                                     - getattr(rt_scl, f))))
                 for f in ("flows", "routed", "dropped", "violations"))

    # carbon vs latency routing, end to end through the autoscaler
    res_pol = {}
    for pol in ("carbon", "latency"):
        cfg_t = TrafficConfig(population=pop, replicas=reps,
                              routing=RoutingConfig(slo_ms=slo_ms,
                                                    policy=pol))
        res_pol[pol] = simulate_traffic(arr.requests, intensity, cfg_t)
    rc, rl = res_pol["carbon"], res_pol["latency"]
    over_cap = int(np.sum(rc.routed > cap * (1.0 + 1e-9)))

    # end-to-end sweep: fleet (NumPy modulation) vs jax (in-scan fold)
    fam = paper_family()
    traces = [t.util for t in sample_population(n_traces, days=days,
                                                seed=5)]
    eng = PlacementEngine(fam, provs, region_names=regions,
                          config=PlacementConfig(capacity=n_traces,
                                                 min_dwell=6))
    pols = {"carbon_containers":
            lambda: CarbonContainerPolicy(variant="energy")}
    cfg = SimConfig(target_rate=0.0)
    tc = TrafficConfig(population=pop, replicas=reps,
                       routing=RoutingConfig(slo_ms=slo_ms))
    sweep_kw = dict(placement=eng, traffic=tc)
    rows_f = sweep_population(pols, fam, traces, None, [30.0, 60.0], cfg,
                              backend="fleet", **sweep_kw)
    rows_j = sweep_population(pols, fam, traces, None, [30.0, 60.0], cfg,
                              backend="jax", **sweep_kw)
    keys = ("carbon_rate_mean", "throttle_mean", "migrations_mean",
            "traffic_served", "traffic_carbon_per_request_g",
            "traffic_slo_violations")
    sweep_parity = max(abs(a[k] - b[k]) / max(abs(a[k]), 1.0)
                       for a, b in zip(rows_f, rows_j) for k in keys)

    rows = [{"routing": pol, "offered": r.offered_total,
             "served": r.served_total, "dropped": r.dropped_total,
             "slo_violations": r.violation_total,
             "emissions_g": r.emissions_total_g,
             "carbon_per_request_g": r.carbon_per_request_g,
             "replica_epochs": float(r.replicas.sum())}
            for pol, r in res_pol.items()]
    derived = {
        "n_users": pop.n_users,
        "n_epochs": T,
        "gen_s": gen_s,
        "speedup_x": scl_s / vec_s,
        "parity_max_abs_diff": parity,
        "cpr_carbon_g": rc.carbon_per_request_g,
        "cpr_latency_g": rl.carbon_per_request_g,
        "cpr_ratio": rc.carbon_per_request_g / rl.carbon_per_request_g,
        "viol_rate_delta": abs(rc.violation_rate - rl.violation_rate),
        "over_capacity_epochs": over_cap,
        "sweep_parity_max_abs_diff": sweep_parity,
    }
    return rows, derived


# ---------------------------------------------------------------------------
# Per-container elasticity: greedy speedup, backend parity, cap
# invariant, oracle-vs-forecast-vs-persistence ablation
# ---------------------------------------------------------------------------

def elasticity_sweep(n_containers: int = 2000, days: int = 10):
    """The elasticity layer's benchmark-gate entry.

    Hourly epochs over multi-day synthetic region traces — the regime
    where the diurnal + AR(1) structure is actually learnable (at
    5-minute epochs the hourly carbon trace is a step function and
    persistence is nearly unbeatable). Four claims in one scenario:

      - `speedup_x` / `parity_max_abs_diff` / `levels_equal`: the
        vectorized (N, K) greedy vs the pure-Python reference on a
        shared column subset (level counts bit-equal).
      - `jax_parity_max_abs_diff` / `jax_levels_equal`: the jitted
        scan vs NumPy on the full fleet, indexed carbon layout.
      - `cap_violations`: the fleet-wide estimated-grams budget is
        never exceeded beyond the mandatory floor, any epoch, any mode.
      - the ablation: carbon per unit of served work for
        oracle/forecast/persistence with *budget shaping* — the same
        total gram budget, reallocated across epochs by each mode's
        now-vs-next-24h carbon forecast. Persistence believes carbon
        stays flat, so its shaped budget is uniform: the baseline is a
        degenerate case, not a separate code path.
        `forecast_savings_frac` = 1 - forecast/persistence must stay
        positive (the headline: knowing the diurnal *structure*
        recovers most of the oracle's advantage), `work_ratio` pins
        the near-equal-work footing.
      - `sweep_parity_max_abs_diff` / `sweep_levels_equal`: the full
        `sweep_population(..., elasticity=)` contract, fleet vs jax
        backends with placement + elasticity composed.
    """
    from repro.carbon.traces import synth_trace
    from repro.core.elasticity import ElasticityConfig, simulate_elastic
    from repro.core.elasticity_jax import simulate_elastic_jax

    T = 24 * days
    regions = ("PL", "NL", "CAISO")
    region_mat = np.stack([synth_trace(r, hours=T, seed=11)
                           for r in regions], axis=1)
    n = n_containers
    rng = np.random.default_rng(7)
    phase = rng.uniform(0.0, 1.0, (1, n))
    base = 2.0 + np.sin(2.0 * np.pi * (np.arange(T)[:, None] / 24.0 + phase))
    # AR(1) residual on top of the diurnal base: the exact structure
    # the "forecast" mode's diurnal_ar1 estimator models
    eps = rng.normal(0.0, 0.3, (T, n))
    noise = np.zeros((T, n))
    for t in range(1, T):
        noise[t] = 0.9 * noise[t - 1] + eps[t]
    demand = np.abs(base + noise)
    codes = np.tile(np.arange(n, dtype=np.int32) % 3, (T, 1))
    carbon = region_mat[np.arange(T)[:, None], codes]

    mk = lambda mode, budget, shape=False: ElasticityConfig(
        k_levels=4, unit_capacity=1.0, base_w=50.0, peak_w=200.0,
        min_level=1, max_step=4, budget_g_per_epoch=budget, forecast=mode,
        shape_budget=shape)

    # budget: 60% of the uncapped oracle's mean estimated grams/epoch,
    # so the greedy genuinely chooses between containers every epoch
    free = simulate_elastic(demand, carbon, mk("oracle", None), 3600.0)
    budget = 0.6 * free.est_emissions_g / T

    # vectorized vs pure-Python reference on a shared subset (the
    # scalar loop walks N*K dict entries per epoch — pure overhead)
    n_par = min(n, 300)
    dsub, csub = demand[:, :n_par], carbon[:, :n_par]
    cfg_par = mk("forecast", budget * n_par / n)
    res_v, vec_s, res_s, scl_s = _best_of_interleaved(
        lambda: simulate_elastic(dsub, csub, cfg_par, 3600.0,
                                 backend="numpy"),
        lambda: simulate_elastic(dsub, csub, cfg_par, 3600.0,
                                 backend="scalar"),
        rounds=3)
    parity = float(np.max(np.abs(res_v.served_w - res_s.served_w)))
    levels_equal = bool(np.array_equal(res_v.levels, res_s.levels))

    # ablation at full width + jax parity on the indexed layout: same
    # total gram budget per mode, shaped by each mode's own forecaster
    cpw, work, viol = {}, {}, 0
    jax_parity = 0.0
    jax_levels_equal = True
    for mode in ("oracle", "forecast", "persistence"):
        cfg_m = mk(mode, budget, shape=True)
        res = simulate_elastic(demand, carbon, cfg_m, 3600.0)
        s = res.summary()
        cpw[mode] = s["elastic_emissions_g"] / max(s["elastic_served_work"],
                                                   1e-12)
        work[mode] = s["elastic_served_work"]
        viol += s["elastic_cap_violations"]
        rj = simulate_elastic_jax(demand, (region_mat, codes), cfg_m,
                                  3600.0, record=True)
        jax_levels_equal &= bool(np.array_equal(res.levels, rj.levels))
        scale = max(float(np.max(np.abs(res.served_w))), 1.0)
        jax_parity = max(jax_parity,
                         float(np.max(np.abs(res.served_w - rj.served_w)))
                         / scale)
        viol += rj.cap_violations

    # end-to-end sweep contract: fleet vs jax with placement+elasticity
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.cluster.slices import paper_family
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig, sweep_population
    from repro.workload.azure_like import sample_population
    fam = paper_family()
    traces = [t.util for t in sample_population(16, days=1, seed=5)]
    provs = [TraceProvider.for_region(r, hours=24, seed=1)
             for r in regions]
    ec = ElasticityConfig(k_levels=4, unit_capacity=0.3,
                          budget_g_per_epoch=100.0, forecast="forecast",
                          shape_budget=True)
    pols = {"carbon_containers":
            lambda: CarbonContainerPolicy(variant="energy")}
    cfg_s = SimConfig(target_rate=0.0)
    mk_eng = lambda: PlacementEngine(
        fam, provs, region_names=regions,
        config=PlacementConfig(capacity=16, min_dwell=6))
    rows_f = sweep_population(pols, fam, traces, None, [30.0, 60.0],
                              cfg_s, backend="fleet", placement=mk_eng(),
                              elasticity=ec)
    rows_j = sweep_population(pols, fam, traces, None, [30.0, 60.0],
                              cfg_s, backend="jax", placement=mk_eng(),
                              elasticity=ec)
    keys = ("carbon_rate_mean", "throttle_mean", "migrations_mean",
            "elastic_served_work", "elastic_emissions_g",
            "elastic_served_frac")
    sweep_parity = max(abs(a[k] - b[k]) / max(abs(a[k]), 1.0)
                       for a, b in zip(rows_f, rows_j) for k in keys)
    sweep_levels_equal = all(
        a["elastic_level_epochs"] == b["elastic_level_epochs"]
        for a, b in zip(rows_f, rows_j))

    rows = [{"mode": m, "carbon_per_work_g": cpw[m], "served_work": work[m]}
            for m in ("oracle", "forecast", "persistence")]
    derived = {
        "n_containers": n,
        "n_epochs": T,
        "budget_g_per_epoch": budget,
        "speedup_x": scl_s / vec_s,
        "parity_max_abs_diff": parity,
        "levels_equal": int(levels_equal),
        "jax_parity_max_abs_diff": jax_parity,
        "jax_levels_equal": int(jax_levels_equal),
        "cap_violations": int(viol),
        "cpw_oracle_g": cpw["oracle"],
        "cpw_forecast_g": cpw["forecast"],
        "cpw_persistence_g": cpw["persistence"],
        "forecast_savings_frac": 1.0 - cpw["forecast"] / cpw["persistence"],
        "oracle_savings_frac": 1.0 - cpw["oracle"] / cpw["persistence"],
        "work_ratio": min(work.values()) / max(work.values()),
        "sweep_parity_max_abs_diff": sweep_parity,
        "sweep_levels_equal": int(sweep_levels_equal),
    }
    return rows, derived


def energy_sweep(n_containers: int = 400, days: int = 4):
    """The virtual energy supply layer's benchmark-gate entry.

    One placed fleet sweep run three ways through the declarative
    `SweepSpec` surface: energy off vs energy on (interleaved best-of
    timing, so `overhead_frac` — the cost of the supply ledger, the
    virtual-cap gather, and the delivered-mix billing — is measured
    under identical host load), then the energy-on sweep again on the
    jax backend. Gated claims:

      - `overhead_frac` <= 0.10: the energy layer costs at most 10% of
        the plain fleet sweep.
      - `energy_conservation_max_err_w` / `energy_cap_violations` /
        `energy_soc_violations`: the supply ledger balances to float
        precision and the software-defined caps and battery bounds hold
        by construction, under a mid-sweep outage and a correlated
        intensity spike.
      - `sweep_parity_max_rel_diff` <= 1e-6: fleet vs jax backends
        agree on every shared numeric row metric with the energy layer
        folded in (read off `SweepResult.parity`, the uniform accessor
        the gate exists to exercise).
    """
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig
    from repro.cluster.slices import paper_family
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig
    from repro.core.spec import SweepSpec
    from repro.energy import EnergyConfig, GridEventConfig
    from repro.workload.azure_like import sample_population_matrix

    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in regions]
    demand = sample_population_matrix(n_containers, days=days, seed=2)
    T = demand.shape[0]
    en = EnergyConfig(events=GridEventConfig(
        outages=((1, T // 4, T // 24),),
        shocks=((-1, T // 2, T // 12, 2.0),)))
    pols = {"carbon_containers":
            lambda: CarbonContainerPolicy(variant="energy")}

    def _spec(backend, energy):
        return SweepSpec(
            policies=pols, family=fam, traces=demand,
            targets=[30.0, 60.0], sim=SimConfig(target_rate=0.0),
            backend=backend,
            placement=PlacementConfig(
                capacity=int(np.ceil(0.6 * n_containers)), min_dwell=6),
            regions=provs, region_names=regions, energy=energy)

    res_off, off_s, res_on, on_s = _best_of_interleaved(
        lambda: _spec("fleet", None).run(),
        lambda: _spec("fleet", en).run(), rounds=3, fast_reps=1)
    res_jax = _spec("jax", en).run()

    r0 = res_on[0]
    derived = {
        "n_containers": n_containers,
        "n_epochs": T,
        "fleet_s": off_s,
        "fleet_energy_s": on_s,
        "overhead_frac": on_s / off_s - 1.0,
        "energy_conservation_max_err_w": r0["energy_conservation_max_err_w"],
        "energy_cap_violations": int(r0["energy_cap_violations"]),
        "energy_soc_violations": int(r0["energy_soc_violations"]),
        "energy_outage_epochs": int(r0["energy_outage_epochs"]),
        "energy_solar_frac": r0["energy_solar_frac"],
        "energy_unmet_frac": r0["energy_unmet_frac"],
        "energy_cap_frac_min": r0["energy_cap_frac_min"],
        "sweep_parity_max_rel_diff": res_on.parity(res_jax),
        "capped_vs_plain_carbon_delta":
            r0["carbon_rate_mean"] - res_off[0]["carbon_rate_mean"],
    }
    return list(res_on), derived


def robustness_sweep(n_traces: int = 96, n_targets: int = 3, days: int = 1):
    """The signal-plane fault-injection benchmark-gate entry.

    One placed fleet sweep run under a 20%-dropout carbon feed (plus a
    trough-anchored blackout, seeded migration failures, and power-
    telemetry gaps), once per degradation mode, on both array backends.
    Gated claims:

      - `ladder_excess_overshoot`: with the graceful-degradation ladder
        (hold -> causal diurnal prior -> conservative floor) the worst
        per-row overshoot of the carbon target stays within a pinned
        bound of the oracle (fault-free) sweep.
      - `hold_excess_overshoot`: naive hold-forever demonstrably blows
        through the target on the same fault plan (the floor pins the
        failure mode the ladder exists to prevent — the blackout lands
        at the intensity trough, so held samples flatter the budget
        precisely while the true grid gets dirtier).
      - `conservative_budget_violations` == 0: under mode
        "conservative" (noise-free faults, traces bounded by c_max) the
        recorded power series never exceeds the true-billed gram
        target, counted per (epoch, container) by
        `repro.robustness.budget_violations`.
      - `sweep_parity_max_rel_diff` <= 1e-6: fleet vs jax agree on
        every shared row metric with the full fault plan enabled
        (degraded feed, failed migrations, unmetered emissions).
    """
    from repro.cluster.placement import PlacementConfig
    from repro.cluster.slices import paper_family
    from repro.core.fleet import FleetSimulator
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig
    from repro.core.spec import SweepSpec
    from repro.robustness import (CarbonFeedFaults, DegradeConfig,
                                  FaultPlan, MigrationFaults,
                                  PowerTelemetryFaults, budget_violations,
                                  observe_intensity)
    from repro.workload.azure_like import sample_population_matrix

    fam = paper_family()
    T = 288 * days
    t = np.arange(T)
    # diurnal grids with a deep trough: the blackout opens at the trough
    # so hold-forever budgets on the day's cleanest reading while the
    # true intensity climbs toward the peak
    phases = (0.0, 1.9, 3.6)
    regions = np.stack([260.0 + 210.0 * np.sin(
        2 * np.pi * t / 288.0 + 2.6 + p) for p in phases], axis=1)
    # mid-day trough: fresh samples exist before the feed goes dark, so
    # hold-forever genuinely holds a flattering reading
    trough = int(np.argmin(regions[:, 0]))
    demand = sample_population_matrix(n_traces, days=days, seed=2)
    # low targets so the gram budget genuinely binds (the workload
    # draws ~7-10 g/hr unconstrained) - overshoot is then a real signal
    targets = list(np.linspace(3.0, 9.0, n_targets))
    policies = {"cc": lambda: CarbonContainerPolicy()}
    cfg = SimConfig(target_rate=0.0)

    def _plan(mode):
        return FaultPlan(
            carbon=CarbonFeedFaults(dropout_prob=0.2,
                                    blackouts=((-1, trough, T // 3),)),
            power=PowerTelemetryFaults(gap_prob=0.05),
            migration=MigrationFaults(fail_prob=0.3, backoff_cap=8),
            degrade=DegradeConfig(mode=mode, ttl_epochs=3,
                                  c_max=float(regions.max())),
            seed=17)

    def _spec(backend, faults):
        return SweepSpec(
            policies=policies, family=fam, traces=demand, targets=targets,
            sim=cfg, backend=backend,
            placement=PlacementConfig(
                capacity=int(np.ceil(0.6 * n_traces)), min_dwell=6),
            regions=regions, faults=faults)

    results = {}
    timings = {}
    for mode in ("oracle", "ladder", "hold", "conservative"):
        faults = None if mode == "oracle" else _plan(mode)
        t0 = time.perf_counter()
        results[mode] = _spec("fleet", faults).run()
        timings[mode] = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_jax = _spec("jax", _plan("ladder")).run()
    jax_s = time.perf_counter() - t0

    # per-epoch overshoot certificate: small recorded runs billed at the
    # TRUE intensity (the sweep path never records (T, N) power at
    # scale). The budget binds per epoch, so the overshoot that matters
    # is max over (epoch, container) of rate/target - 1: hold-forever
    # keeps budgeting on the trough reading while the true grid climbs,
    # the ladder degrades to the prior/floor instead.
    n_small = min(16, n_traces)
    true_c = regions[:, 0]
    sim = FleetSimulator(fam)
    tgt_small = np.repeat(targets, n_small)
    dem_small = np.tile(demand[:, :n_small], (1, n_targets))

    # the first epochs pay the scale-down from the baseline slice -- an
    # actuation transient every mode (incl. the oracle) shares, so the
    # certificate starts once the actuator has settled
    settle = 4

    def _recorded_overshoot(mode):
        if mode == "oracle":
            obs = None
        else:
            sig = observe_intensity(true_c[:, None], _plan(mode), 300.0)
            obs = sig.observed[:, 0]
        rec = sim.run(CarbonContainerPolicy(), dem_small, true_c,
                      tgt_small, record=True, carbon_obs=obs)
        rate = rec.power_series[settle:] * true_c[settle:, None] / 1000.0
        over = float(np.max(rate / tgt_small[None, :] - 1.0))
        viol = budget_violations(rec.power_series[settle:],
                                 true_c[settle:], tgt_small, 300.0)
        return max(0.0, over), viol

    over = {}
    viols = {}
    for mode in ("oracle", "ladder", "hold", "conservative"):
        over[mode], viols[mode] = _recorded_overshoot(mode)
    viol = viols["conservative"]
    r0 = results["ladder"][0]
    rows = [{"mode": m, "overshoot": over[m], "wall_s": timings[m],
             **{k: r[k] for k in ("policy", "target", "carbon_rate_mean")}}
            for m in results for r in results[m]]
    derived = {
        "n_containers": n_traces * n_targets,
        "n_epochs": T,
        "dropout_prob": 0.2,
        "steady_s": timings["ladder"],
        "jax_s": jax_s,
        "oracle_overshoot": over["oracle"],
        "ladder_overshoot": over["ladder"],
        "hold_overshoot": over["hold"],
        "conservative_overshoot": over["conservative"],
        "ladder_excess_overshoot": over["ladder"] - over["oracle"],
        "hold_excess_overshoot": over["hold"] - over["oracle"],
        "conservative_budget_violations": viol,
        "fault_stale_frac": r0["fault_stale_frac"],
        "fault_failed_migrations_mean": r0["fault_failed_migrations_mean"],
        "fault_unmetered_g_mean": r0["fault_unmetered_g_mean"],
        "sweep_parity_max_rel_diff": results["ladder"].parity(res_jax),
    }
    return rows, derived
