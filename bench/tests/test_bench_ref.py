"""The plain reference (`bench.ref.placed`) on small cases: a plan worked
out by hand, and agreement with two witnesses of the program that it
shares no code with, its scalar chain and its NumPy fleet backend."""
import numpy as np
import pytest

from bench import cells, check, sweep
from bench.gen.fleet import make_inputs
from bench.ref import placed

SEED = 2**33 + 4242


def _cfg(n_traces):
    bench = cells.load()
    return {**cells.config(bench, "fleet_1m_r3"), "n_traces": n_traces}


def test_admission_follows_capacity_and_container_order():
    cfg = {**_cfg(4), "capacity": {"share": 0.75}}      # 3 slots a region
    demand = np.full((2, 4), 0.5)
    regions = np.array([[800.0, 50.0], [800.0, 50.0]])
    plan = placed.region_plan(cfg, demand, regions)
    # round-robin start [0, 1, 0, 1]; both region-0 containers ask for the
    # clean region, which has one free slot: the first in order gets it
    assert plan["assign"].tolist() == [[1, 1, 0, 1], [1, 1, 0, 1]]
    assert plan["migrations"].tolist() == [1, 0, 0, 0]
    cost0 = 2.0 * 100.0 * 11.6 / 3600.0
    assert plan["overhead_g"][0] == pytest.approx(cost0 * 425.0 / 1000.0)
    assert check.over_capacity_epochs(plan["assign"], 2, 3) == 0


def test_migration_downtime_is_fig7_at_the_link():
    cfg = _cfg(4)
    assert placed.downtime_s(cfg, 0.25) == pytest.approx(11.6)
    assert placed.downtime_s(cfg, 1.0) == pytest.approx(11.225)


class _Hourly:
    """A carbon provider over per-epoch values, for the scalar chain."""

    def __init__(self, values, dt):
        self.values, self.dt = values, dt

    def intensity(self, t):
        return float(self.values[int(round(t / self.dt))])


def test_reference_matches_the_scalar_chain():
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig, simulate
    cfg = _cfg(24)
    inputs = make_inputs(cfg, SEED)
    target = inputs["targets"][3]
    rows, plan = placed.sweep(cfg, inputs, [target])
    spec = sweep.program_sweep(cfg, {"name": "placed", "layers": {}}, inputs)
    eng = spec.placement
    splan = eng.plan_scalar(inputs["traces"], state_gb=1.0)
    assert np.array_equal(splan.assign, plan["assign"])
    assert np.array_equal(splan.migrations, plan["migrations"])
    assert np.allclose(splan.overhead_g, plan["overhead_g"], rtol=1e-12)

    dt = cfg["sim"]["interval_s"]
    carbon = splan.carbon_matrix()
    res = [simulate(CarbonContainerPolicy(variant="energy", min_dwell=2,
                                          idle_margin=0.02),
                    sweep.family(cfg), inputs["traces"][:, i],
                    _Hourly(carbon[:, i], dt),
                    SimConfig(target_rate=target, epsilon=0.05,
                              interval_s=dt, state_gb=1.0))
           for i in range(24)]
    row = rows[0]
    assert row["carbon_rate_mean"] == pytest.approx(
        np.mean([r.avg_carbon_rate for r in res]), rel=1e-12)
    assert row["throttle_std"] == pytest.approx(
        np.std([r.avg_throttle_pct for r in res]), rel=1e-12, abs=1e-12)
    assert row["migrations_mean"] == np.mean([r.migrations for r in res])
    assert row["suspended_frac_mean"] == pytest.approx(
        np.mean([r.suspended_frac for r in res]), abs=1e-15)
    for name, share in row["time_on_slice"].items():
        assert share == pytest.approx(np.mean(
            [r.time_on_slice.get(name, 0.0) for r in res]), rel=1e-12)


@pytest.mark.parametrize("seed", [SEED, SEED + 1])
def test_reference_matches_the_fleet_backend(seed):
    cfg = _cfg(900)
    inputs = make_inputs(cfg, seed)
    spec = sweep.program_sweep(cfg, {"name": "placed", "layers": {}}, inputs)
    spec.backend = "fleet"
    got = spec.run().rows
    ref, plan = placed.sweep(cfg, inputs, inputs["targets"])
    gap = check.rows_gap(got, ref)
    assert gap["count_mismatches"] == 0 and gap["missing"] == 0
    assert gap["row_rel_gap"] < 1e-12
    assert check.over_capacity_epochs(plan["assign"], 3,
                                      placed.capacity(cfg)) == 0
