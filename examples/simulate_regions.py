"""Large-scale Carbon Containers simulation across regions (paper Figs 11-16
in miniature): per-region policy tables, a heterogeneous fleet — mixed
regions (stacked carbon traces), mixed targets, mixed demand scales — run
through the vectorized FleetSimulator, a multi-region *placement* demo
where the fleet migrates between low- and high-variability grids, and a
device-resident JAX sweep over a 10k-container placed fleet
(``--jax-sweep``, or ``make jax-sweep``).

    PYTHONPATH=src python examples/simulate_regions.py \
        [--jobs 20] [--backend fleet|scalar] [--fleet 120] [--placement] \
        [--jax-sweep]
"""
import sys
import time

import numpy as np

from repro.carbon.intensity import TraceProvider
from repro.cluster.placement import PlacementConfig, PlacementEngine
from repro.cluster.slices import paper_family
from repro.compile_cache import enable_compile_cache
from repro.core.fleet import FleetSimulator
from repro.core.policy import (CarbonAgnosticPolicy, CarbonContainerPolicy,
                               SuspendResumePolicy, VScaleOnlyPolicy)
from repro.core.simulator import SimConfig, simulate
from repro.workload.azure_like import sample_population

DAYS = 5
INTERVAL_S = 300.0


def _arg(flag, default, cast):
    if flag in sys.argv:
        return cast(sys.argv[sys.argv.index(flag) + 1])
    return default


def per_region_tables(n_jobs: int, backend: str):
    """The original per-region policy comparison, now fleet-backed."""
    fam = paper_family()
    traces = [t.util for t in sample_population(n_jobs, days=DAYS, seed=2)]
    policies = [
        ("carbon-agnostic", CarbonAgnosticPolicy),
        ("suspend/resume", SuspendResumePolicy),
        ("vscale-only", lambda: VScaleOnlyPolicy()),
        ("CC (energy)", lambda: CarbonContainerPolicy("energy")),
        ("CC (performance)", lambda: CarbonContainerPolicy("performance")),
    ]
    target = 45.0
    print(f"{n_jobs} jobs x {DAYS} days, C_target = {target} g/hr "
          f"[backend={backend}]\n")
    for region in ("PL", "NL", "CAISO"):
        carbon = TraceProvider.for_region(region, hours=24 * DAYS, seed=1)
        print(f"--- region {region} ---")
        print(f"  {'policy':18s} {'g/hr':>8s} {'throttle%':>10s} "
              f"{'migs':>6s} {'susp%':>6s}")
        for name, mk in policies:
            if backend == "fleet":
                sim = FleetSimulator(fam, interval_s=INTERVAL_S)
                res = sim.run(mk(), np.stack(traces, axis=1), carbon, target,
                              state_gb=1.0)
                rates = res.avg_carbon_rate
                thr = res.avg_throttle_pct
                migs = res.migrations
                susp = res.suspended_frac
            else:
                rates, thr, migs, susp = [], [], [], []
                for tr in traces:
                    r = simulate(mk(), fam, tr, carbon,
                                 SimConfig(target_rate=target, state_gb=1.0))
                    rates.append(r.avg_carbon_rate)
                    thr.append(r.avg_throttle_pct)
                    migs.append(r.migrations)
                    susp.append(r.suspended_frac)
            print(f"  {name:18s} {np.mean(rates):8.2f} {np.mean(thr):10.2f} "
                  f"{np.mean(migs):6.1f} {100 * np.mean(susp):6.1f}")
        print()


def heterogeneous_fleet(n: int):
    """One batched run over a mixed fleet: container i gets a region, a
    carbon target and a demand scale of its own — the multi-tenant
    (Ecovisor-style energy partitioning / CarbonScaler elasticity) shape,
    expressed as stacked carbon traces + per-container target vectors."""
    rng = np.random.default_rng(7)
    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = {r: TraceProvider.for_region(r, hours=24 * DAYS, seed=1)
             for r in regions}
    traces = [t.util for t in sample_population(n, days=DAYS, seed=3)]
    T = len(traces[0])
    tvec = np.arange(T) * INTERVAL_S

    assign = rng.integers(0, len(regions), size=n)
    cmat = np.stack([provs[regions[a]].intensity_series(tvec)
                     for a in assign], axis=1)
    targets = rng.choice([20.0, 35.0, 50.0, 80.0], size=n)
    demand_scale = rng.choice([0.5, 1.0, 2.0, 4.0], size=n)
    state_gb = rng.choice([0.25, 1.0, 4.0], size=n)

    sim = FleetSimulator(fam, interval_s=INTERVAL_S)
    res = sim.run(CarbonContainerPolicy("energy"), np.stack(traces, axis=1),
                  cmat, targets, state_gb=state_gb,
                  demand_scale=demand_scale)

    print(f"--- heterogeneous fleet: {n} containers, mixed "
          f"{'/'.join(regions)}, mixed targets/scales ---")
    print(f"  {'group':22s} {'n':>4s} {'g/hr':>8s} {'target':>7s} "
          f"{'throttle%':>10s} {'susp%':>6s}")
    for ri, region in enumerate(regions):
        m = assign == ri
        if not m.any():
            continue
        print(f"  region {region:15s} {int(m.sum()):4d} "
              f"{res.avg_carbon_rate[m].mean():8.2f} "
              f"{targets[m].mean():7.1f} "
              f"{res.avg_throttle_pct[m].mean():10.2f} "
              f"{100 * res.suspended_frac[m].mean():6.1f}")
    for tgt in np.unique(targets):
        m = targets == tgt
        print(f"  target {tgt:5.0f} g/hr     {int(m.sum()):4d} "
              f"{res.avg_carbon_rate[m].mean():8.2f} "
              f"{tgt:7.1f} "
              f"{res.avg_throttle_pct[m].mean():10.2f} "
              f"{100 * res.suspended_frac[m].mean():6.1f}")
    under = (res.avg_carbon_rate <= targets * 1.02).mean()
    print(f"\n  fleet emissions: {res.emissions_g.sum() / 1000.0:.1f} kg CO2e"
          f" | {100 * under:.0f}% of containers within 2% of target\n")


def multi_region_placement(n: int):
    """A heterogeneous fleet free to migrate between a dirty low-variability
    grid (PL: coal, flat) and cleaner high-variability ones (NL, CAISO):
    the PlacementEngine moves containers toward the cleanest region whose
    projected saving beats the amortized stop-and-copy cost, under
    per-region capacity, and the same fleet frozen on its initial regions
    is the no-migration baseline."""
    rng = np.random.default_rng(11)
    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * DAYS, seed=1)
             for r in regions]
    traces = [t.util for t in sample_population(n, days=DAYS, seed=5)]
    demand = np.stack(traces, axis=1)
    targets = rng.choice([30.0, 45.0, 80.0], size=n)
    state_gb = rng.choice([0.25, 1.0, 4.0], size=n)

    cap = int(np.ceil(0.6 * n))          # no region may hold the whole fleet
    eng = PlacementEngine(
        fam, provs, interval_s=INTERVAL_S, region_names=regions,
        config=PlacementConfig(capacity=cap, min_dwell=6, hysteresis=0.10))
    res = eng.run(CarbonContainerPolicy("energy"), demand, targets,
                  state_gb=state_gb, compare_static=True)
    plan, fleet, static = res.plan, res.fleet, res.static_fleet

    occ = plan.occupancy()
    print(f"--- multi-region placement: {n} containers over "
          f"{'/'.join(regions)}, capacity {cap}/region ---")
    print(f"  {'region':10s} {'occ@start':>9s} {'occ@end':>8s} "
          f"{'avg g/kWh':>10s}")
    for r, name in enumerate(regions):
        print(f"  {name:10s} {occ[0, r]:9d} {occ[-1, r]:8d} "
              f"{plan.region_intensity[:, r].mean():10.0f}")
    moved_kg = res.total_emissions_g.sum() / 1000.0
    static_kg = static.emissions_g.sum() / 1000.0
    print(f"  placement moves: {int(plan.migrations.sum())} "
          f"(downtime {plan.downtime_s.sum():.0f} s, "
          f"overhead {plan.overhead_g.sum():.1f} g)")
    print(f"  emissions: placed {moved_kg:.1f} kg vs static {static_kg:.1f} "
          f"kg -> {res.saving_vs_static_pct:.1f}% saved")
    eff_m = float(res.carbon_efficiency.mean())
    eff_s = float((static.work_done
                   / np.maximum(static.emissions_g / 1000.0, 1e-12)).mean())
    print(f"  carbon-efficiency (work/kg CO2e): placed {eff_m:.0f} vs "
          f"static {eff_s:.0f} ({100.0 * (eff_m / eff_s - 1.0):+.1f}%)\n")


def jax_sweep(n_containers: int = 10080, n_targets: int = 12,
              days: int = 3):
    """A 10k-container placed fleet sweep, device-resident end-to-end:
    the JAX placement kernel assigns every trace column a region per
    epoch, then one jit/scan per policy sweeps all (target x trace)
    columns — against the same sweep on the NumPy fleet backend."""
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig
    from repro.core.spec import SweepSpec

    from repro.workload.azure_like import sample_population_matrix

    n_traces = n_containers // n_targets
    fam = paper_family()
    regions = ("PL", "NL", "CAISO")
    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in regions]
    # (T, n_traces) matrix straight through the sweep — the vectorized
    # generator is what makes 100k-trace fleets feasible (make jax-sweep
    # runs this same path at N=1M via benchmarks.run)
    traces = sample_population_matrix(n_traces, days=days, seed=3)
    T = traces.shape[0]
    cap = int(np.ceil(0.6 * n_traces))
    eng = PlacementEngine(
        fam, provs, interval_s=INTERVAL_S, region_names=regions,
        config=PlacementConfig(capacity=cap, min_dwell=6, hysteresis=0.10))
    targets = list(np.linspace(20.0, 80.0, n_targets))
    policies = {"CC (energy)":
                lambda: CarbonContainerPolicy(variant="energy")}
    cfg = SimConfig(target_rate=0.0)
    n_total = n_traces * n_targets

    print(f"--- jax sweep: {n_total} placed containers "
          f"({n_traces} traces x {n_targets} targets, {T} epochs, "
          f"capacity {cap}/region) ---")
    spec = SweepSpec(policies=policies, family=fam, traces=traces,
                     targets=targets, sim=cfg, backend="jax", placement=eng)
    t0 = time.perf_counter()
    rows = spec.run()
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows = spec.run()
    steady = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows_np = SweepSpec(policies=policies, family=fam, traces=traces,
                        targets=targets, sim=cfg, backend="fleet",
                        placement=eng).run()
    numpy_s = time.perf_counter() - t0
    drift = max(abs(a["carbon_rate_mean"] - b["carbon_rate_mean"])
                for a, b in zip(rows, rows_np))
    rate = n_total * T / steady
    print(f"  jax:   first call {warm:.2f}s (jit compile), steady "
          f"{steady:.2f}s  ({rate/1e6:.1f}M container-epochs/s)")
    print(f"  numpy: {numpy_s:.2f}s  -> {numpy_s/steady:.1f}x steady-state "
          f"speedup (parity drift {drift:.1e})")
    print(f"\n  {'target':>7s} {'g/hr':>8s} {'throttle%':>10s} "
          f"{'migs':>6s} {'placement migs':>14s}")
    for r in rows:
        print(f"  {r['target']:7.1f} {r['carbon_rate_mean']:8.2f} "
              f"{r['throttle_mean']:10.2f} {r['migrations_mean']:6.1f} "
              f"{r['placement_migrations_mean']:14.1f}")
    print()


def main():
    enable_compile_cache()
    n_jobs = _arg("--jobs", 20, int)
    backend = _arg("--backend", "fleet", str)
    if backend not in ("fleet", "scalar"):
        raise SystemExit(f"--backend must be 'fleet' or 'scalar', "
                         f"got {backend!r}")
    n_fleet = _arg("--fleet", 120, int)
    if "--jax-sweep" in sys.argv:        # jax demo only (make jax-sweep)
        # CPU-tuned XLA flags, set before jax initializes; explicit
        # user settings win
        from repro.core.fleet_jax import ensure_cpu_xla_flags
        ensure_cpu_xla_flags()
        jax_sweep(_arg("--containers", 10080, int))
        return
    if "--placement" in sys.argv:        # placement demo only (make placement)
        multi_region_placement(n_fleet)
        return
    per_region_tables(n_jobs, backend)
    heterogeneous_fleet(n_fleet)
    multi_region_placement(n_fleet)


if __name__ == "__main__":
    main()
