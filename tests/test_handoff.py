"""The hand-off from the JAX planner to the fleet scan.

`plan_jax` leaves the (T, N) assignments and the demand it pushed on the
device, and `sweep_population_jax` hands both to `FleetSimulatorJax.run`
instead of pulling them to the host and pushing them up again. The rows
must be the rows of the same sweep run on host copies, bit for bit, on
every path that takes the plan's codes; the plan's host `assign` must
keep its contract; the `handoff_bytes` counter says where it engaged.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import obs
from repro.cluster import placement_jax
from repro.cluster.placement import PlacementConfig, PlacementEngine
from repro.cluster.slices import paper_family
from repro.core.elasticity import ElasticityConfig
from repro.core.fleet_jax import FleetSimulatorJax
from repro.core.policy import CarbonAgnosticPolicy, CarbonContainerPolicy
from repro.core.simulator import SimConfig, sweep_population
from repro.energy import EnergyConfig, GridEventConfig
from repro.robustness import (CarbonFeedFaults, DegradeConfig, FaultPlan,
                              MigrationFaults, PowerTelemetryFaults)
from repro.traffic import TrafficConfig, UserPopulation

T, N_TR, TARGETS = 96, 24, (30.0, 60.0)
POLICIES = {"cc": lambda: CarbonContainerPolicy("energy"),
            "agnostic": CarbonAgnosticPolicy}
_TRAFFIC = TrafficConfig(population=UserPopulation(n_users=5000,
                                                   n_regions=3, seed=3))
_ENERGY = EnergyConfig(events=GridEventConfig(outages=((1, 20, 6),),
                                              shocks=((-1, 50, 12, 2.0),)))
_ELASTIC = ElasticityConfig(k_levels=4, unit_capacity=0.3,
                            budget_g_per_epoch=60.0, forecast="forecast",
                            shape_budget=True)
_FAULTS = FaultPlan(
    carbon=CarbonFeedFaults(dropout_prob=0.25,
                            blackouts=((-1, T // 3, T // 8),)),
    power=PowerTelemetryFaults(gap_prob=0.1),
    migration=MigrationFaults(fail_prob=0.4, backoff_cap=8),
    degrade=DegradeConfig(mode="ladder", ttl_epochs=3), seed=17)

# every path whose fleet scan takes the plan's codes
PATHS = {
    "placed": {},
    "traffic_in_scan": {"traffic": _TRAFFIC},
    "energy_in_scan": {"energy": _ENERGY},
    "faults": {"faults": _FAULTS},
    "energy_elastic": {"energy": _ENERGY, "elasticity": _ELASTIC},
}


def _inputs(seed=1):
    rng = np.random.default_rng(seed)
    traces = rng.uniform(0.2, 1.6, size=(T, N_TR))
    t = np.linspace(0, 4 * np.pi, T)
    regions = np.stack([200 + 150 * np.sin(t + p)
                        for p in (0.0, 1.5, 3.0)], axis=1) + 50.0
    eng = PlacementEngine(paper_family(), regions, interval_s=300.0,
                          config=PlacementConfig(capacity=N_TR // 2,
                                                 min_dwell=4))
    return traces, eng


def _sweep(placed=True, **layers):
    traces, eng = _inputs()
    return sweep_population(POLICIES, paper_family(), traces,
                            None if placed else eng.regions[:, 0],
                            list(TARGETS), SimConfig(target_rate=0.0),
                            backend="jax",
                            placement=eng if placed else None, **layers)


def _host_copies(monkeypatch):
    """Run the fleet scan on host copies of the codes and the demand, as
    a sweep did before the hand-off."""
    orig = FleetSimulatorJax.run

    def run(self, policy, demand, carbon, *a, demand_device=None, **k):
        if isinstance(carbon, tuple):
            carbon = (carbon[0], np.asarray(carbon[1]))
        return orig(self, policy, demand, carbon, *a, **k)
    monkeypatch.setattr(FleetSimulatorJax, "run", run)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_rows_with_the_handoff_equal_rows_on_host_copies(path,
                                                          monkeypatch):
    handed = _sweep(**PATHS[path])
    assert obs.last_sweep()["handoff_bytes"] > 0
    _host_copies(monkeypatch)
    host = _sweep(**PATHS[path])
    assert obs.last_sweep()["handoff_bytes"] == 0
    assert handed == host


def _tap(monkeypatch):
    plans = []
    orig = placement_jax.plan_jax

    def tapped(*a, **k):
        plans.append(orig(*a, **k))
        return plans[-1]
    monkeypatch.setattr(placement_jax, "plan_jax", tapped)
    return plans


def test_plan_assign_is_a_writable_int64_host_array_after_the_sweep(
        monkeypatch):
    plans = _tap(monkeypatch)
    _sweep()
    (plan,) = plans
    # nothing on the placed path read the host copy
    assert plan._assign is None
    a = plan.assign
    assert type(a) is np.ndarray and a.dtype == np.int64
    assert a.shape == (T, N_TR) and a.flags.writeable
    traces, eng = _inputs()
    np.testing.assert_array_equal(a, eng.plan(traces).assign)
    # made once, then kept: a write is seen by the next read
    a[0, 0] = (a[0, 0] + 1) % 3
    assert plan.assign is a and plan.assign[0, 0] == a[0, 0]


@pytest.mark.parametrize("layers,per_cell", [
    ({}, 8 + 4),                                    # demand and codes
    ({"energy": _ENERGY, "elasticity": _ELASTIC}, 4),   # codes only
    ({"placed": False}, 0),                         # no plan, no hand-off
])
def test_handoff_bytes_count_what_the_scan_took_from_the_plan(layers,
                                                              per_cell):
    _sweep(**layers)
    # one fleet scan per policy, each takes the arrays up
    assert obs.last_sweep()["handoff_bytes"] == (
        len(POLICIES) * T * N_TR * per_cell)


_SHARDED = textwrap.dedent("""
    import jax
    from repro import obs
    from repro.core import fleet_jax
    from tests.test_handoff import N_TR, POLICIES, T, _host_copies, _sweep

    class Patch:
        def setattr(self, obj, name, value):
            setattr(obj, name, value)

    assert len(jax.devices()) == 4
    handed = _sweep()
    fleet_jax._MIN_SHARD_COLS = 2
    sharded = _sweep()
    # two shards (one per target), each given the demand and the codes
    assert obs.last_sweep()["handoff_bytes"] == (
        len(POLICIES) * 2 * T * N_TR * (8 + 4))
    _host_copies(Patch())
    host = _sweep()
    assert handed == sharded == host
    print("ok")
""")


def test_sharded_handoff_on_forced_host_devices():
    """With the fleet split over several devices, each shard gets the
    plan's device arrays by a device-to-device copy; the rows stay the
    one-device rows."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(root, "src"), root]))
    out = subprocess.run([sys.executable, "-c", _SHARDED], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("ok")
