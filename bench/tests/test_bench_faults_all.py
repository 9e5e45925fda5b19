"""Faults of the layers that only `r3_all` runs, made in the timed path
underneath a CPU run of the cell at 240 traces: the comparison calls each
wrong. A level the elasticity scan allocated is not served, the battery
sits out the first epoch it would discharge in, the elasticity scan admits
every optional level whatever the budget, and the carbon feed's dropouts
land one epoch late. The budget binds in few epochs of this deployment:
at this size and seed it binds, and the comparison sees the greedy's cut."""
import time

import jax
import numpy as np
import pytest

from bench import cells, run
from repro.core import elasticity_jax
from repro.energy import supply
from repro.robustness import degrade

SIZES = {"n_traces": 240}
SEED = 2**33 + 97


def _run():
    return run.run_cell(cells.load(), "r3_all", SEED, 0.0, False,
                        jax.devices(), {}, time.perf_counter(), sizes=SIZES)


def _level_dropped(monkeypatch):
    orig = elasticity_jax.simulate_elastic_jax

    def dropped(*a, **k):
        res = orig(*a, **k)
        cfg = a[2]
        cap = cfg.unit_capacity * res.interval_s
        t, i = np.unravel_index(np.argmax(res.served_w), res.served_w.shape)
        res.served_w = res.served_w.copy()
        res.served_w[t, i] = max(res.served_w[t, i] - cap, 0.0)
        return res
    monkeypatch.setattr(elasticity_jax, "simulate_elastic_jax", dropped)


def _battery_step_skipped(monkeypatch):
    step0, simulate0 = supply.supply_step_scalar, supply.simulate_supply
    done = [False]

    def step(spec, soc, *a):
        soc1, out = step0(spec, soc, *a)
        if not done[0] and out[2] > 0.0:        # the first discharge
            done[0] = True
            # the battery sits the epoch out: the grid takes the deficit
            return step0(spec._replace(max_charge_w=0.0,
                                       max_discharge_w=0.0), soc, *a)
        return soc1, out

    def simulate(*a, **k):
        done[0] = False
        return simulate0(*a, **k)
    monkeypatch.setattr(supply, "supply_step_scalar", step)
    monkeypatch.setattr(supply, "simulate_supply", simulate)


def _budget_ignored(monkeypatch):
    orig = elasticity_jax._prepare

    def unbounded(*a, **k):
        xs, T, n, R = orig(*a, **k)
        # every optional level fits: the greedy admits all it may
        xs = xs[:2] + (np.full_like(xs[2], np.inf),) + xs[3:]
        return xs, T, n, R
    monkeypatch.setattr(elasticity_jax, "_prepare", unbounded)


def _dropouts_late(monkeypatch):
    orig = degrade.carbon_fault_masks

    def late(*a, **k):
        fresh, noise = orig(*a, **k)
        return np.roll(fresh, 1, axis=0), noise
    monkeypatch.setattr(degrade, "carbon_fault_masks", late)


@pytest.mark.parametrize("fault", [_level_dropped, _battery_step_skipped,
                                   _budget_ignored, _dropouts_late])
def test_broken_layer_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _run()
    # every sweep is broken alike: the reference, not the repeat, sees it
    assert out["failed"] == 0
    assert not out["correct"], out["checks"]
