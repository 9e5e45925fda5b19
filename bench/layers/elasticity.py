"""The elasticity layer: K levels per container allocated each epoch by
the CarbonScaler greedy under a forecast-shaped gram budget, unserved work
deferred (`SweepSpec.elasticity`). Its settings are the configuration's
`layers.elasticity`; the budget is stated per trace and epoch. It draws
nothing."""


def inputs(cfg, params, seeds):
    return {}


def program(cfg, params, inputs):
    from repro.core.elasticity import ElasticityConfig
    e = dict(cfg["layers"]["elasticity"])
    per_trace = e.pop("budget_g_per_epoch_per_trace")
    return {"elasticity": ElasticityConfig(
        budget_g_per_epoch=per_trace * int(cfg["n_traces"]), **e)}
