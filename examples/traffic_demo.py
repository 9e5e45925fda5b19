"""Carbon-aware traffic demo (``make traffic``).

A 1M-user population spread over three regions eight time-zone-hours
apart offers a diurnal, bursty request stream. Requests are routed
per epoch by carbon intensity under an SLO latency bound (vs a
latency-only baseline), per-region replica fleets autoscale to the
routed load, and the resulting serving load modulates container demand
through the placed fleet sweep:

    user demand (requests) --> SLO-constrained routing --> replica
    autoscaling --> per-region serving load --> container demand
    modulation --> placed fleet simulation

    PYTHONPATH=src python examples/traffic_demo.py [--users 1000000]
        [--days 1] [--budget <g/epoch>]
"""
import sys

import numpy as np

from repro.carbon.intensity import TraceProvider
from repro.cluster.placement import PlacementConfig, PlacementEngine
from repro.cluster.slices import paper_family
from repro.compile_cache import enable_compile_cache
from repro.core.policy import CarbonContainerPolicy
from repro.core.simulator import SimConfig
from repro.core.spec import SweepSpec
from repro.traffic import (RoutingConfig, TrafficConfig, UserPopulation,
                           request_matrix, simulate_traffic)
from repro.traffic.autoscale import ReplicaConfig

INTERVAL_S = 300.0
REGIONS = ("PL", "NL", "CAISO")


def _arg(flag, default, cast):
    if flag in sys.argv:
        return cast(sys.argv[sys.argv.index(flag) + 1])
    return default


def main():
    enable_compile_cache()
    n_users = _arg("--users", 1_000_000, int)
    days = _arg("--days", 1, int)
    budget = _arg("--budget", None, float)
    T = int(days * 86400 / INTERVAL_S)

    provs = [TraceProvider.for_region(r, hours=24 * days, seed=1)
             for r in REGIONS]
    intensity = np.stack(
        [p.intensity_series(np.arange(T) * INTERVAL_S) for p in provs],
        axis=1)
    pop = UserPopulation(n_users=n_users, n_regions=3,
                         tz_offset_h=(0.0, 8.0, 16.0), seed=3)
    reps = ReplicaConfig(max_replicas=8, max_step=4,
                         budget_g_per_epoch=budget)
    arr = request_matrix(pop, T, INTERVAL_S)
    print(f"population: {n_users:,} users, {arr.offered_total:,.0f} "
          f"requests over {days} day(s), regions {REGIONS}")

    print(f"\n{'routing':>10} {'served':>14} {'dropped':>12} "
          f"{'SLO viol':>10} {'g CO2/1k req':>13}")
    results = {}
    for pol in ("carbon", "latency"):
        cfg = TrafficConfig(population=pop, replicas=reps,
                            routing=RoutingConfig(slo_ms=200.0, policy=pol))
        res = simulate_traffic(arr.requests, intensity, cfg, INTERVAL_S)
        results[pol] = res
        print(f"{pol:>10} {res.served_total:>14,.0f} "
              f"{res.dropped_total:>12,.0f} {res.violation_total:>10,.0f} "
              f"{1000.0 * res.carbon_per_request_g:>13.3f}")
    rc, rl = results["carbon"], results["latency"]
    saved = 1.0 - rc.carbon_per_request_g / rl.carbon_per_request_g
    print(f"\ncarbon routing emits {100.0 * saved:.1f}% less per request "
          f"than latency routing at the same SLO-violation rate")

    # the same traffic driving the placed fleet sweep end to end
    from repro.workload.azure_like import sample_population
    fam = paper_family()
    traces = [t.util for t in sample_population(24, days=days, seed=5)]
    eng = PlacementEngine(fam, provs, region_names=REGIONS,
                          config=PlacementConfig(capacity=24, min_dwell=6))
    tc = TrafficConfig(population=pop, replicas=reps,
                       routing=RoutingConfig(slo_ms=200.0))
    rows = SweepSpec(
        policies={"carbon_containers":
                  lambda: CarbonContainerPolicy("energy")},
        family=fam, traces=traces, targets=[30.0, 60.0],
        sim=SimConfig(target_rate=0.0), backend="fleet", placement=eng,
        traffic=tc).run()
    print("\nplaced fleet sweep with traffic-modulated demand:")
    for r in rows:
        print(f"  target {r['target']:>5.1f}: carbon rate "
              f"{r['carbon_rate_mean']:.2f} g/h, throttle "
              f"{r['throttle_mean']:.2f}%, carbon/request "
              f"{1000.0 * r['traffic_carbon_per_request_g']:.3f} g/1k")


if __name__ == "__main__":
    main()
