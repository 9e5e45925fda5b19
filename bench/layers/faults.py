"""The fault layer: carbon-feed dropouts and blackouts degraded by the
ladder, power-meter gaps and failing migrations (`SweepSpec.faults`). Its
settings are the configuration's `layers.faults`; the masks draw from the
run's `faults` stream, each fault class at the stream's seed plus its
salt. The salts are the program's constants, which the configuration must
state as they are."""


def inputs(cfg, params, seeds):
    return {}


def program(cfg, params, inputs):
    from repro.robustness import (CarbonFeedFaults, DegradeConfig, FaultPlan,
                                  MigrationFaults, PowerTelemetryFaults)
    from bench.fixed import refuse_unless
    from repro.robustness import faults
    f = cfg["layers"]["faults"]
    refuse_unless("layers.faults.salts", f["salts"], {
        "dropout": faults._SALT_DROPOUT, "noise": faults._SALT_NOISE,
        "migration": faults._SALT_MIG, "gap": faults._SALT_GAP})
    c, p, m = f["carbon"], f["power"], f["migration"]
    return {"faults": FaultPlan(
        carbon=CarbonFeedFaults(
            dropout_prob=c["dropout_prob"],
            blackouts=tuple(tuple(b) for b in c["blackouts"]),
            stale_every=c["stale_every"],
            noise_windows=tuple(tuple(w) for w in c["noise_windows"])),
        power=PowerTelemetryFaults(gap_prob=p["gap_prob"],
                                   gaps=tuple(tuple(g) for g in p["gaps"])),
        migration=MigrationFaults(fail_prob=m["fail_prob"],
                                  backoff_base=m["backoff_base"],
                                  backoff_cap=m["backoff_cap"]),
        degrade=DegradeConfig(**f["degrade"]),
        seed=inputs["seeds"]["faults"])}
