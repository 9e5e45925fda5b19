"""The traffic layer: a user population routed across the regions under a
latency SLO and served by autoscaled replicas, whose serving load scales
each container's demand (`SweepSpec.traffic`). Its settings are the
configuration's `layers.traffic`; the population draws from the run's
`population` stream. The diurnal peak hour and the noise's AR(1)
coefficient are the program's constants, which the configuration must
state as they are."""


def inputs(cfg, params, seeds):
    return {}


def program(cfg, params, inputs):
    import inspect

    from bench.fixed import refuse_unless
    from repro.traffic import (ReplicaConfig, RoutingConfig, TrafficConfig,
                               UserPopulation, arrivals)
    from repro.workload.azure_like import ar1_burst_factors
    t = cfg["layers"]["traffic"]
    p = t["population"]
    refuse_unless("layers.traffic.population.peak_hour_local",
                  p["peak_hour_local"], arrivals._PEAK_HOUR_LOCAL)
    refuse_unless("layers.traffic.population.ar1_rho", p["ar1_rho"],
                  inspect.signature(ar1_burst_factors).parameters["rho"]
                  .default)
    pop = UserPopulation(
        n_users=p["n_users"], n_regions=p["n_regions"],
        region_weights=tuple(p["region_weights"]),
        tz_offset_h=tuple(p["tz_offset_h"]),
        req_per_user_day=p["req_per_user_day"],
        rate_lognorm_sigma=p["rate_lognorm_sigma"],
        peak_to_trough=p["peak_to_trough"], cov=p["cov"],
        normalize=p["normalize"], seed=inputs["seeds"]["population"])
    return {"traffic": TrafficConfig(
        population=pop, routing=RoutingConfig(**t["routing"]),
        replicas=ReplicaConfig(**t["replicas"]),
        latency_ms=tuple(tuple(row) for row in t["latency_ms"]),
        demand_gain=t["demand_gain"])}
