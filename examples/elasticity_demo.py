"""Per-container elasticity demo (``make elasticity``).

A fleet of containers, each with K discrete resource levels, scaled
every epoch by the CarbonScaler marginal-allocation greedy: flatten
the (N, K) table of marginal work / marginal grams, admit levels in
descending carbon-efficiency order under a fleet-wide gram budget.
The budget itself is *shaped* — the same total grams reallocated
across the day by the forecaster's now-vs-next-24h carbon ratio — so
the quality of the forecast decides how much work lands in green
hours:

    demand + carbon traces --> forecasters (d-hat, c-hat, shaped
    budget) --> (N, K) marginal greedy --> levels, served work,
    deferred backlog --> emissions at the true intensity

Runs the oracle / forecast / persistence ablation (persistence
believes carbon stays flat, so its shaped budget degenerates to
uniform — the unshaped baseline), then the same layer composed with
placement inside the fleet sweep on both backends.

    PYTHONPATH=src python examples/elasticity_demo.py
        [--containers 2000] [--days 10] [--budget-frac 0.6]
"""
import sys

import numpy as np

from repro.carbon.traces import synth_trace
from repro.compile_cache import enable_compile_cache
from repro.core.elasticity import ElasticityConfig, simulate_elastic

INTERVAL_S = 3600.0
REGIONS = ("PL", "NL", "CAISO")


def _arg(flag, default, cast):
    if flag in sys.argv:
        return cast(sys.argv[sys.argv.index(flag) + 1])
    return default


def main():
    enable_compile_cache()
    n = _arg("--containers", 2000, int)
    days = _arg("--days", 10, int)
    frac = _arg("--budget-frac", 0.6, float)
    T = 24 * days

    region_mat = np.stack([synth_trace(r, hours=T, seed=11)
                           for r in REGIONS], axis=1)
    rng = np.random.default_rng(7)
    phase = rng.uniform(0.0, 1.0, (1, n))
    base = 2.0 + np.sin(2 * np.pi * (np.arange(T)[:, None] / 24.0 + phase))
    eps = rng.normal(0.0, 0.3, (T, n))
    noise = np.zeros((T, n))
    for t in range(1, T):
        noise[t] = 0.9 * noise[t - 1] + eps[t]
    demand = np.abs(base + noise)
    codes = np.tile(np.arange(n, dtype=np.int32) % 3, (T, 1))
    carbon = region_mat[np.arange(T)[:, None], codes]
    print(f"fleet: {n:,} containers x {T} hourly epochs, "
          f"K=4 levels, regions {REGIONS}")

    mk = lambda mode, budget, shape=False: ElasticityConfig(
        k_levels=4, unit_capacity=1.0, base_w=50.0, peak_w=200.0,
        max_step=4, budget_g_per_epoch=budget, forecast=mode,
        shape_budget=shape)
    free = simulate_elastic(demand, carbon, mk("oracle", None), INTERVAL_S)
    budget = frac * free.est_emissions_g / T
    print(f"budget: {budget:,.0f} g/epoch shaped "
          f"({frac:.0%} of the uncapped oracle estimate)")

    print(f"\n{'forecaster':>12} {'kg CO2':>10} {'g/unit work':>12} "
          f"{'served':>8} {'deferred':>9} {'cap viol':>9}")
    cpw = {}
    for mode in ("oracle", "forecast", "persistence"):
        s = simulate_elastic(demand, carbon, mk(mode, budget, True),
                             INTERVAL_S).summary()
        cpw[mode] = (s["elastic_emissions_g"]
                     / max(s["elastic_served_work"], 1e-12))
        print(f"{mode:>12} {s['elastic_emissions_g'] / 1e3:>10.1f} "
              f"{cpw[mode]:>12.5f} {s['elastic_served_frac']:>7.1%} "
              f"{s['elastic_deferred_work']:>9.0f} "
              f"{s['elastic_cap_violations']:>9d}")
    print(f"\nforecast saves {1 - cpw['forecast'] / cpw['persistence']:.2%} "
          f"carbon per unit work vs persistence "
          f"(oracle bound {1 - cpw['oracle'] / cpw['persistence']:.2%}): "
          f"knowing the diurnal shape moves the budget into green hours")

    # same layer composed with placement inside the sweep, both backends
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.cluster.slices import paper_family
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig
    from repro.core.spec import SweepSpec
    from repro.workload.azure_like import sample_population

    fam = paper_family()
    traces = [t.util for t in sample_population(64, days=1, seed=5)]
    provs = [TraceProvider.for_region(r, hours=24, seed=1)
             for r in REGIONS]
    ec = ElasticityConfig(k_levels=4, unit_capacity=0.3,
                          budget_g_per_epoch=150.0, forecast="forecast",
                          shape_budget=True)
    pols = {"carbon_containers":
            lambda: CarbonContainerPolicy(variant="energy")}
    print(f"\nplaced sweep with elasticity (64 traces, both backends):")
    for backend in ("fleet", "jax"):
        rows = SweepSpec(policies=pols, family=fam, traces=traces,
                         targets=[40.0], sim=SimConfig(target_rate=0.0),
                         backend=backend,
                         placement=PlacementConfig(capacity=64, min_dwell=6),
                         regions=provs, region_names=REGIONS,
                         elasticity=ec).run()
        r = rows[0]
        print(f"  {backend:>6}: carbon_rate={r['carbon_rate_mean']:.2f} "
              f"served={r['elastic_served_frac']:.1%} "
              f"level_epochs={r['elastic_level_epochs']} "
              f"cap_viol={r['elastic_cap_violations']}")


if __name__ == "__main__":
    main()
