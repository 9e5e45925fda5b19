"""The all-layer reference (`bench.ref.all`) and its layers' references:
the `r3_all` cell through `run_cell` on the CPU at 240 traces, cases
worked by hand for each layer's rule, agreement with the program's own
layer modules on shared inputs, a reference with a layer left out, which
the comparison calls wrong, and a program constant stated otherwise in
the configuration, which the layer refuses."""
import copy
import time

import jax
import numpy as np
import pytest

from bench import cells, check, run, sweep
from bench.gen.fleet import make_inputs
from bench.ref import all as ref
from bench.ref import elastic, energy, faults, traffic

SIZES = {"n_traces": 240}
SEEDS = (2**33 + 181, 3180000777)


def _cfg(**sizes):
    bench = cells.load()
    return {**cells.config(bench, "fleet_1m_r3_all"), **SIZES, **sizes}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_runs_correct(seed):
    out = run.run_cell(cells.load(), "r3_all", seed, 0.0, False,
                       jax.devices(), {}, time.perf_counter(), sizes=SIZES)
    assert out["correct"], out["checks"]
    assert out["checks"]["row_rel_gap"]["value"] < 1e-12
    assert out["checks"]["plan_mismatches"]["value"] == 0


@pytest.fixture(scope="module")
def program():
    """One all-layer sweep of the timed path at 240 traces: its inputs,
    rows and plan."""
    cfg = _cfg()
    mix = cells.mix("all")
    inputs = make_inputs(cfg, SEEDS[0], mix)
    with sweep.PlanTap() as tap:
        rows = sweep.program_sweep(cfg, mix, inputs).run().rows
    return cfg, inputs, rows, tap.last.assign


@pytest.mark.parametrize("layer, path, value", [
    ("traffic", ("population", "peak_hour_local"), 14.0),
    ("traffic", ("population", "ar1_rho"), 0.9),
    ("energy", ("soc_snap_wh",), 1e-6),
    ("faults", ("salts", "gap"), 7)])
def test_a_program_constant_stated_otherwise_is_refused(layer, path, value):
    """The program takes these as constants; a configuration that states
    another value is refused at set-up, naming the key."""
    cfg = copy.deepcopy(_cfg())
    node = cfg["layers"][layer]
    for key in path[:-1]:
        node = node[key]
    mix = cells.mix("all")
    module = cells.layers(mix)[layer]
    inputs = {"seeds": {"population": 1, "energy": 2, "faults": 3}}
    module.program(cfg, mix["layers"][layer], inputs)
    node[path[-1]] = value
    with pytest.raises(ValueError, match=path[-1]):
        module.program(cfg, mix["layers"][layer], inputs)


@pytest.mark.parametrize("off", ref.LAYERS)
def test_a_reference_without_one_layer_is_not_correct(program, off):
    cfg, inputs, rows, assign = program
    targets = check.sampled_targets(inputs["targets"], 2, SEEDS[0])
    full, plan = ref.sweep(cfg, inputs, targets)
    assert check.rows_gap(rows, full, ref.COUNT_KEYS)["row_rel_gap"] < 1e-12
    less, less_plan = ref.layers_sweep(
        cfg, inputs, targets, on=tuple(n for n in ref.LAYERS if n != off))
    plans = {"plan_mismatches": check.plan_mismatches(assign,
                                                      less_plan["assign"]),
             "over_capacity_epochs": 0}
    v = check.judge(rows, [rows], less, cfg["limits"], 0, plans,
                    ref.COUNT_KEYS)
    assert not v["correct"], v["checks"]


def test_elastic_greedy_on_three_containers_by_hand():
    """K = 2, an hour an epoch (cap 3600 work a level), 50-200 W a level.
    Every container holds level 1 (mandatory) and may take level 2.
    Level 1 runs full: 200 W, at 1000, 2000 and 1000 g/kWh that is 200,
    400 and 200 g (800 mandatory). Level 2 serves 3600, 3600 and 1800 work
    for 200, 400 and 125 g: work per gram 18, 9 and 14.4, so the order is
    containers 0, 2, 1."""
    e = {"k_levels": 2, "unit_capacity": 1.0, "base_w": 50.0,
         "peak_w": 200.0, "min_level": 1, "max_step": 1}
    want = np.array([7200.0, 7200.0, 5400.0])
    chat = np.array([1000.0, 2000.0, 1000.0])
    prev = np.ones(3)
    for budget, levels in ((999.0, [1, 1, 1]), (1000.0, [2, 1, 1]),
                           (1124.9, [2, 1, 1]), (1125.0, [2, 1, 2]),
                           (1525.0, [2, 2, 2])):
        got, lo = elastic.allocate(e, want, chat, prev, budget, 3600.0)
        assert got.tolist() == levels, budget
        assert lo.tolist() == [1, 1, 1]
    # a level at zero grams is free: taken first, whatever the budget
    got, _ = elastic.allocate(e, want, np.array([1000.0, 0.0, 1000.0]),
                              prev, 400.0, 3600.0)
    assert got.tolist() == [1, 2, 1]
    # ramp: from level 2 a container may fall to 1 but not to 0
    got, lo = elastic.allocate(e, np.zeros(3), chat, np.full(3, 2.0), 0.0,
                               3600.0)
    assert got.tolist() == lo.tolist() == [1, 1, 1]


def test_one_battery_step_charging_then_discharging():
    """An hour an epoch, a 100 Wh battery at 20 Wh, 60 W rates, 90%
    charge efficiency. Epoch 0: 20 W of load under 100 W of sun; 60 W of
    the surplus charges (the rate binds before the 88.9 W headroom): SoC
    20 + 54 = 74. Epoch 1: 100 W of load under 10 W of sun; the battery
    gives its 60 W, the grid the last 30 W, at a third of the delivered
    power. Epoch 2 in an outage: sun 10, battery 14 (all it holds), no
    grid: 24 W of 100 delivered, all of it carbon-free."""
    sz = {"cap_wh": 100.0, "charge_w": 60.0, "discharge_w": 60.0,
          "eta": 0.9, "soc0_wh": 20.0, "snap_wh": 1e-9}
    load = np.array([[20.0], [100.0], [100.0]])
    sun = np.array([[100.0], [10.0], [10.0]])
    grid_c = np.full((3, 1), 400.0)
    up = np.array([[1.0], [1.0], [0.0]])
    led = energy.supply(sz, load, sun, grid_c, up, 3600.0)
    assert led["charge"][:, 0].tolist() == [60.0, 0.0, 0.0]
    assert led["discharge"][:, 0] == pytest.approx([0.0, 60.0, 14.0])
    assert led["grid"][:, 0] == pytest.approx([0.0, 30.0, 0.0])
    assert led["soc"][:, 0] == pytest.approx([74.0, 14.0, 0.0])
    assert led["cap"][:, 0] == pytest.approx([1.0, 1.0, 0.24])
    assert led["c_eff"][:, 0] == pytest.approx([0.0, 120.0, 0.0])
    s = energy.summary(led, sz, 3600.0)
    assert s["energy_conservation_max_err_w"] == 0.0
    assert s["energy_outage_epochs"] == 1
    assert s["energy_cap_violations"] == s["energy_soc_violations"] == 0


def test_ladder_falls_from_fresh_to_hold_to_prior_to_floor():
    """Four epochs a day; region 0 blacked out over epochs [2, 6), hold
    for 1 epoch, the prior for 3. Epoch 2 holds 20; epoch 3 has no sample
    in its slot yet, so the prior is the mean of all received (15); epoch
    4's slot saw 10 a day ago; epoch 5 is past the prior: the floor."""
    f = {"carbon": {"dropout_prob": 0.0, "blackouts": [[0, 2, 4]],
                    "stale_every": 1, "noise_windows": []},
         "degrade": {"mode": "ladder", "ttl_epochs": 1,
                     "prior_ttl_epochs": 3, "c_max": 999.0},
         "salts": {"dropout": 1, "noise": 2, "migration": 3, "gap": 4}}
    true = np.arange(10.0, 90.0, 10.0)[:, None]
    sig = faults.observe(f, 5, true, 6 * 3600.0)
    assert sig["observed"][:, 0].tolist() == [10, 20, 20, 15, 10, 999, 70,
                                              80]
    assert sig["tier"][:, 0].tolist() == [0, 0, 1, 2, 2, 3, 0, 0]
    assert sig["age"][:, 0].tolist() == [0, 0, 1, 2, 3, 4, 0, 0]
    s = faults.summary(sig)
    assert s["fault_stale_frac"] == 0.5 and s["fault_max_age"] == 4


@pytest.mark.parametrize("spill", [True, False])
def test_routing_spills_past_the_slo(spill):
    """Capacity 100 a region; sources 0 and 1 reach regions 0 and 1
    within the SLO, source 2 only itself. Rank 1: region 1 (cleanest)
    takes 100 of source 0's 150 and none of source 1's 80 (source 0's want
    came first); region 2 takes source 2's 30. Rank 2: region 0 takes
    source 0's last 50 and 50 of source 1's. Rank 3: source 1's last 30
    go to region 2, outside the SLO, or are dropped without spill."""
    lat = np.array([[20.0, 100.0, 200.0], [100.0, 20.0, 200.0],
                    [200.0, 200.0, 20.0]])
    got = traffic.route(np.array([[150.0, 80.0, 30.0]]), 100.0,
                        np.array([[300.0, 100.0, 50.0]]), lat,
                        {"slo_ms": 150.0, "policy": "carbon",
                         "spill": spill})
    if spill:
        assert got["routed"].tolist() == [[100.0, 100.0, 60.0]]
        assert got["dropped"].tolist() == [[0.0, 0.0, 0.0]]
        assert got["outside"].tolist() == [[0.0, 30.0, 0.0]]
    else:
        assert got["routed"].tolist() == [[100.0, 100.0, 30.0]]
        assert got["dropped"].tolist() == [[0.0, 30.0, 0.0]]
        assert got["outside"].tolist() == [[0.0, 0.0, 0.0]]


def test_autoscaling_ramps_within_its_step():
    rep = {"throughput_rps": 1.0, "base_w": 60.0, "peak_w": 120.0,
           "max_replicas": 8, "min_replicas": 1, "max_step": 4,
           "budget_g_per_epoch": None}
    routed = np.array([[7000.0], [7000.0], [0.0]])     # 1000 a replica
    sc = traffic.autoscale(routed, np.full((3, 1), 500.0), rep, 1000.0)
    assert sc["replicas"][:, 0].tolist() == [5.0, 7.0, 3.0]
    assert sc["served"][:, 0].tolist() == [5000.0, 7000.0, 0.0]


# --- the layers' references against the program's own modules ----------

def test_arrivals_match_the_program_generator():
    from repro.traffic.arrivals import UserPopulation, request_matrix
    p = _cfg()["layers"]["traffic"]["population"]
    pop = UserPopulation(n_users=30_000, n_regions=3,
                         region_weights=tuple(p["region_weights"]),
                         tz_offset_h=tuple(p["tz_offset_h"]), seed=77)
    t = {"population": {**p, "n_users": 30_000}}
    np.testing.assert_allclose(traffic.arrivals(t, 77, 288, 300.0),
                               request_matrix(pop, 288, 300.0).requests,
                               rtol=1e-13)


def test_supply_and_events_match_the_program():
    from repro.energy.supply import (EnergySpec, SolarConfig, event_matrices,
                                     simulate_supply, solar_series)
    from repro.energy.supply import EnergyConfig, GridEventConfig
    cfg = _cfg()
    e = cfg["layers"]["energy"]
    ev = GridEventConfig(outages=((1, 96, 12),), shocks=((-1, 144, 24, 1.6),))
    for got, want in zip(energy.events(e, 288, 3),
                         event_matrices(ev, 288, 3)):
        np.testing.assert_array_equal(got, want)
    sz = energy.sizes(e, cfg, 3)
    spec = EnergySpec.from_config(EnergyConfig(), 240, 3, 300.0,
                                  sz["w_per_unit"])
    sun = energy.solar(e, 9, 288, 3, 300.0, sz["solar_peak_w"])
    np.testing.assert_array_equal(
        sun, solar_series(SolarConfig(seed=9), 288, 3, 300.0,
                          spec.solar_peak_w))
    rng = np.random.default_rng(4)
    load = rng.uniform(0.0, 3.0 * sz["solar_peak_w"], (288, 3))
    grid_c = rng.uniform(50.0, 800.0, (288, 3))
    up = energy.events(e, 288, 3)[1]
    led = energy.supply(sz, load, sun, grid_c, up, 300.0)
    res = simulate_supply(load, sun, grid_c, up, spec)
    for k, a in (("delivered", "supplied"), ("cap", "cap_frac"),
                 ("c_eff", "c_eff"), ("soc", "soc")):
        np.testing.assert_array_equal(led[k], getattr(res, a))
    assert energy.summary(led, sz, 300.0) == res.summary()


def test_elasticity_matches_the_program_numpy_layer():
    from repro.core.elasticity import ElasticityConfig, simulate_elastic
    e = _cfg()["layers"]["elasticity"]
    rng = np.random.default_rng(6)
    T, n = 288, 60
    demand = rng.uniform(0.0, 1.0, (T, n))
    carbon = rng.uniform(0.0, 600.0, (T, n))
    budget = np.full(T, 400.0)
    chat = elastic.diurnal_ar1(carbon, 288, e["rho"])
    row, served = elastic.simulate(e, demand, carbon, chat, budget, 300.0)
    kw = {k: v for k, v in e.items() if k != "budget_g_per_epoch_per_trace"}
    res = simulate_elastic(demand, carbon,
                           ElasticityConfig(budget_g_per_epoch=400.0, **kw),
                           300.0, carbon_forecast=chat,
                           budget_series=budget)
    want = res.summary()
    assert row["elastic_level_epochs"] == want["elastic_level_epochs"]
    # the budget binds: more than the mandatory levels, fewer than asked
    free, _ = elastic.simulate(e, demand, carbon, chat, budget * 1e9, 300.0)
    assert (T * n < row["elastic_level_epochs"]
            < free["elastic_level_epochs"])
    for k, v in want.items():
        assert row[k] == pytest.approx(v, rel=1e-12, abs=1e-12), k
    np.testing.assert_allclose(served, res.demand_served(), rtol=1e-12)


def test_fault_masks_and_ladder_match_the_program():
    from repro.robustness import (CarbonFeedFaults, DegradeConfig, FaultPlan,
                                  MigrationFaults, PowerTelemetryFaults)
    from repro.robustness.degrade import observe_intensity
    from repro.robustness.faults import (migration_failure_mask,
                                         power_gap_vector)
    f = _cfg()["layers"]["faults"]
    plan = FaultPlan(
        carbon=CarbonFeedFaults(dropout_prob=0.2, blackouts=((-1, 96, 24),)),
        power=PowerTelemetryFaults(gap_prob=0.05),
        migration=MigrationFaults(fail_prob=0.2, backoff_cap=8),
        degrade=DegradeConfig(ttl_epochs=3), seed=2**32 - 5)
    true = np.random.default_rng(1).uniform(50.0, 900.0, (288, 3))
    sig = faults.observe(f, plan.seed, true, 300.0)
    want = observe_intensity(true, plan, 300.0)
    np.testing.assert_array_equal(sig["observed"], want.observed)
    np.testing.assert_array_equal(sig["tier"], want.tier)
    assert faults.summary(sig) == want.summary()
    np.testing.assert_array_equal(faults.meter_gaps(f, plan.seed, 288),
                                  power_gap_vector(plan, 288))
    np.testing.assert_array_equal(
        faults.migration_failures(f, plan.seed, 288, 50),
        migration_failure_mask(plan, 288, 50))
