"""Carbon-aware serving: a request queue with minutes-scale load swings
(the paper's workload-intensity argument) served under a carbon cap.

The scheduler feeds queue-implied demand into the Carbon Container policy;
the policy answers with slice + duty decisions; real batched generation
runs on the engine at the allowed rate.

    PYTHONPATH=src python examples/carbon_serve.py
"""
import numpy as np

from repro.carbon.intensity import TraceProvider
from repro.cluster.slices import paper_family
from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.core.container import ContainerState, PlantModel
from repro.core.policy import CarbonContainerPolicy
from repro.models import get_model
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import CarbonAwareScheduler, poisson_arrivals


def main():
    enable_compile_cache()
    spec = get_arch("smollm-135m")
    engine = ServeEngine(get_model(spec.smoke)).load()
    # calibrate capacity: measured decode throughput = duty-1.0 capacity
    prompts = np.zeros((4, 8), np.int32)
    engine.generate(prompts, 4)
    tok_s = engine.stats["decode_tokens"] / max(engine.stats["decode_s"], 1e-9)

    fam = paper_family()
    policy = CarbonContainerPolicy(variant="energy")
    state = ContainerState(slice_idx=fam.baseline_idx)
    carbon = TraceProvider.for_region("CAISO", hours=48, seed=3)
    sch = CarbonAwareScheduler(capacity_tok_s=tok_s)

    # bursty arrivals: lambda doubles mid-day
    target = 45.0
    interval = 300.0
    print(f"decode capacity {tok_s:.0f} tok/s; C_target {target} g/hr\n")
    print(f"  {'hour':>5s} {'c g/kWh':>8s} {'demand':>7s} {'slice':>6s} "
          f"{'duty':>5s} {'C g/hr':>7s} {'backlog':>7s}")
    rng = np.random.default_rng(0)
    emissions, hours_total = 0.0, 0.0
    for n in range(96):                       # 8 hours of 5-min intervals
        t = n * interval
        lam = 0.03 * (3.0 if 30 <= n < 60 else 1.0)
        for a in poisson_arrivals(lam, interval, seed=n):
            sch.offer(t + a, max_new=32)
        c = carbon.intensity(t)
        demand = min(sch.demand(interval), 4.0)
        state.observe_demand(demand)
        action = policy.decide(fam, state, demand, c, target, 0.05)
        if action.kind == "migrate":
            state.slice_idx = action.target_slice
            state.dwell = 0
        state.duty = action.duty if action.kind in ("stay", "migrate", "resume") else 0.0
        state.suspended = action.kind == "suspend"
        state.dwell += 1
        s = fam[state.slice_idx]
        res = sch.run_interval(state.duty if not state.suspended else 0.0,
                               s.multiple, interval)
        served_util = min(res["util"], s.multiple)
        power = 0.0 if state.suspended else s.power.power(
            min(served_util / s.multiple, 1.0))
        rate = PlantModel.rate(power, c)
        emissions += rate * interval / 3600.0
        hours_total += interval / 3600.0
        if n % 8 == 0:
            print(f"  {t/3600:5.1f} {c:8.0f} {demand:7.2f} {s.name:>6s} "
                  f"{state.duty:5.2f} {rate:7.1f} {res['backlog']:7d}")
    lat = sch.latency_stats()
    print(f"\navg C(t) = {emissions/hours_total:.1f} g/hr (target {target}); "
          f"served {lat['n']} requests, p95 latency {lat['p95_s']:.0f}s")


if __name__ == "__main__":
    main()
