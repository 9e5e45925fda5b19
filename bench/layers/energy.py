"""The energy layer: each region's solar array, battery and grid, with
scheduled outages and carbon shocks (`SweepSpec.energy`). Its settings are
the configuration's `layers.energy`; the solar weather draws from the
run's `energy` stream. The drained-battery snap is the program's
constant, which the configuration must state as it is."""


def inputs(cfg, params, seeds):
    return {}


def program(cfg, params, inputs):
    from repro.energy import (BatteryConfig, EnergyConfig, GridEventConfig,
                              SolarConfig)
    from bench.fixed import refuse_unless
    from repro.energy import supply
    e = cfg["layers"]["energy"]
    refuse_unless("layers.energy.soc_snap_wh", e["soc_snap_wh"],
                  supply.SOC_SNAP_WH)
    s = dict(e["solar"], tz_offset_h=tuple(e["solar"]["tz_offset_h"]))
    ev = e["events"]
    return {"energy": EnergyConfig(
        solar=SolarConfig(**s, seed=inputs["seeds"]["energy"]),
        battery=BatteryConfig(**e["battery"]),
        events=GridEventConfig(
            outages=tuple(tuple(o) for o in ev["outages"]),
            shocks=tuple(tuple(s) for s in ev["shocks"]),
            n_random_outages=ev["n_random_outages"],
            n_random_shocks=ev["n_random_shocks"]))}
