"""JAX fleet backend: parity against the NumPy fleet backend.

The chain is anchored in two hops: the NumPy fleet backend is pinned
bit-compatible (1e-9) to the scalar loop by tests/test_fleet.py, and the
JAX backend is pinned here to 1e-6 against the NumPy backend (the jit
path reassociates loop-invariant scalings, so it is not bit-identical —
observed drift is ~1e-10). Discrete outcomes (migration counts) must
match exactly: a single flipped decision would diverge the whole
trajectory.

The fleets under test bake in the edge cases the closed-form suite also
covers: one zero-demand column and one budget-exhausted (tiny-target)
column ride along in every parity run.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.carbon.intensity import ConstantProvider, TraceProvider  # noqa: E402
from repro.cluster.placement import PlacementConfig, PlacementEngine  # noqa: E402
from repro.cluster.slices import paper_family, tpu_v5e_family  # noqa: E402
from repro.core.fleet import FleetSimulator  # noqa: E402
from repro.core import fleet_jax  # noqa: E402
from repro.core.fleet_jax import FleetSimulatorJax  # noqa: E402
from repro.core.policy import (CarbonAgnosticPolicy, CarbonContainerPolicy,  # noqa: E402
                               SuspendResumePolicy, VScaleOnlyPolicy)
from repro.core.simulator import SimConfig, sweep_population  # noqa: E402
from repro.workload.azure_like import sample_population  # noqa: E402

TOL = 1e-6
DAYS = 1

POLICIES = {
    "carbon_agnostic": CarbonAgnosticPolicy,
    "suspend_resume": SuspendResumePolicy,
    "vscale_only": lambda: VScaleOnlyPolicy(),
    "cc_energy": lambda: CarbonContainerPolicy("energy"),
    "cc_performance": lambda: CarbonContainerPolicy("performance"),
}

PARITY_FIELDS = ("emissions_g", "energy_wh", "work_done", "work_demanded",
                 "throttled_integral", "suspended_s", "elapsed_s")


def _fleet_inputs(n=6, days=DAYS, seed=2):
    """Heterogeneous fleet with the edge columns baked in: column 0 has
    zero demand everywhere, column 1 runs with a budget-exhausting tiny
    target."""
    traces = [t.util for t in sample_population(n, days=days, seed=seed)]
    demand = np.stack(traces, axis=1)
    demand[:, 0] = 0.0                          # zero-demand edge case
    targets = np.linspace(10.0, 80.0, n)
    targets[1] = 1e-6                           # budget exhaustion edge case
    sgb = (np.arange(n) % 4 + 1) * 0.5
    carbon = TraceProvider.for_region("CAISO", hours=24 * days, seed=1)
    return demand, targets, sgb, carbon


def _assert_close(rf, rj, ctx=""):
    for f in PARITY_FIELDS:
        diff = float(np.abs(getattr(rf, f) - getattr(rj, f)).max())
        assert diff <= TOL, f"{ctx}: {f} drifts {diff}"
    assert (rf.migrations == rj.migrations).all(), ctx
    assert float(np.abs(rf.time_on_slice_s - rj.time_on_slice_s).max()) \
        <= TOL, ctx
    assert rf.slice_names == rj.slice_names


@pytest.mark.parametrize("policy_name", sorted(POLICIES))
def test_jax_matches_fleet(policy_name):
    mk = POLICIES[policy_name]
    fam = paper_family()
    demand, targets, sgb, carbon = _fleet_inputs()
    rf = FleetSimulator(fam).run(mk(), demand, carbon, targets,
                                 state_gb=sgb)
    rj = FleetSimulatorJax(fam).run(mk(), demand, carbon, targets,
                                    state_gb=sgb)
    _assert_close(rf, rj, ctx=policy_name)


def test_jax_matches_fleet_hold_slice_and_mixed_regions():
    """suspend_releases_slice=False + a (T, N) per-container carbon
    matrix (mixed-region fleet) + TPU family in one run."""
    fam = tpu_v5e_family()
    demand, targets, sgb, _ = _fleet_inputs(n=4)
    T = demand.shape[0]
    tvec = np.arange(T) * 300.0
    provs = [TraceProvider.for_region(r, hours=24 * DAYS, seed=1)
             for r in ("PL", "NL", "CAISO")]
    cmat = np.stack([provs[i % 3].intensity_series(tvec)
                     for i in range(4)], axis=1)
    targets = targets * 40.0                    # TPU-scale targets
    mk = lambda: CarbonContainerPolicy("energy")
    rf = FleetSimulator(fam, suspend_releases_slice=False).run(
        mk(), demand, cmat, targets, state_gb=sgb)
    rj = FleetSimulatorJax(fam, suspend_releases_slice=False).run(
        mk(), demand, cmat, targets, state_gb=sgb)
    _assert_close(rf, rj, ctx="hold-slice mixed-region tpu")


def test_jax_record_series_matches_and_conserves():
    fam = paper_family()
    demand, targets, sgb, carbon = _fleet_inputs(n=4)
    mk = lambda: CarbonContainerPolicy("energy")
    rf = FleetSimulator(fam).run(mk(), demand, carbon, targets,
                                 state_gb=sgb, record=True)
    rj = FleetSimulatorJax(fam).run(mk(), demand, carbon, targets,
                                    state_gb=sgb, record=True)
    assert rj.power_series.shape == rf.power_series.shape
    assert float(np.abs(rf.power_series - rj.power_series).max()) <= TOL
    assert float(np.abs(rf.served_series - rj.served_series).max()) <= TOL
    # conservation on the jax side
    assert (rj.served_series >= 0.0).all()
    assert (rj.power_series >= 0.0).all()
    assert np.allclose(rj.work_done + rj.throttled_integral,
                       rj.work_demanded, rtol=1e-9, atol=1e-6)


def test_sweep_population_jax_matches_fleet():
    fam = paper_family()
    traces = [t.util for t in sample_population(4, days=DAYS, seed=2)]
    carbon = TraceProvider.for_region("CAISO", hours=24 * DAYS, seed=1)
    pols = {"carbon_agnostic": CarbonAgnosticPolicy,
            "suspend_resume": SuspendResumePolicy,
            "carbon_containers": lambda: CarbonContainerPolicy("energy")}
    targets = [25.0, 55.0]
    cfgb = SimConfig(target_rate=0.0)
    rows_f = sweep_population(pols, fam, traces, carbon, targets, cfgb,
                              backend="fleet")
    rows_j = sweep_population(pols, fam, traces, carbon, targets, cfgb,
                              backend="jax")
    assert len(rows_f) == len(rows_j)
    for a, b in zip(rows_f, rows_j):
        assert a["policy"] == b["policy"] and a["target"] == b["target"]
        for k in ("carbon_rate_mean", "carbon_rate_std", "throttle_mean",
                  "throttle_std", "migrations_mean", "suspended_frac_mean"):
            assert abs(a[k] - b[k]) <= TOL, (a["policy"], a["target"], k)
        for k in set(a["time_on_slice"]) | set(b["time_on_slice"]):
            assert abs(a["time_on_slice"].get(k, 0.0)
                       - b["time_on_slice"].get(k, 0.0)) <= TOL


def test_sweep_population_jax_with_placement_matches_fleet():
    fam = paper_family()
    traces = [t.util for t in sample_population(4, days=DAYS, seed=5)]
    provs = [TraceProvider.for_region(r, hours=24 * DAYS, seed=1)
             for r in ("PL", "NL", "CAISO")]
    eng = PlacementEngine(fam, provs,
                          config=PlacementConfig(capacity=3, min_dwell=4))
    pols = {"carbon_containers": lambda: CarbonContainerPolicy("energy")}
    cfgb = SimConfig(target_rate=0.0)
    rows_f = sweep_population(pols, fam, traces, None, [30.0, 60.0], cfgb,
                              backend="fleet", placement=eng)
    rows_j = sweep_population(pols, fam, traces, None, [30.0, 60.0], cfgb,
                              backend="jax", placement=eng)
    for a, b in zip(rows_f, rows_j):
        for k in ("carbon_rate_mean", "throttle_mean", "migrations_mean",
                  "placement_migrations_mean", "placement_overhead_g_mean"):
            assert abs(a[k] - b[k]) <= TOL, k


def test_jax_rejects_custom_policy():
    class Custom(CarbonContainerPolicy):
        pass

    fam = paper_family()
    with pytest.raises(TypeError):
        FleetSimulatorJax(fam).run(Custom(), np.ones((4, 2)),
                                   ConstantProvider(100.0), 45.0)


def test_jax_rejects_negative_demand_and_bad_carbon():
    fam = paper_family()
    with pytest.raises(ValueError):
        FleetSimulatorJax(fam).run(CarbonAgnosticPolicy(),
                                   np.array([[0.5], [-0.1]]),
                                   ConstantProvider(100.0), 45.0)
    with pytest.raises(ValueError):
        FleetSimulatorJax(fam).run(CarbonAgnosticPolicy(), np.ones((4, 2)),
                                   np.ones((3, 2)), 45.0)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 XLA host devices "
                           "(XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=2)")
def test_jax_sharded_matches_unsharded(monkeypatch):
    """Container-axis sharding concatenates bit-identically."""
    fam = paper_family()
    demand, targets, sgb, carbon = _fleet_inputs(n=6)
    mk = lambda: CarbonContainerPolicy("energy")
    r1 = FleetSimulatorJax(fam).run(mk(), demand, carbon, targets,
                                    state_gb=sgb)
    monkeypatch.setattr(fleet_jax, "_MIN_SHARD_COLS", 2)
    r2 = FleetSimulatorJax(fam).run(mk(), demand, carbon, targets,
                                    state_gb=sgb)
    for f in PARITY_FIELDS:
        assert (getattr(r1, f) == getattr(r2, f)).all(), f
    assert (r1.migrations == r2.migrations).all()
    assert (r1.time_on_slice_s == r2.time_on_slice_s).all()
