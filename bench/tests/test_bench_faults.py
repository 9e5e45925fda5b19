"""A run of each cell, driven on the CPU at a few hundred traces past the
look for a chip, is correct; with the timed path broken underneath, the
comparison calls it wrong. The faults a one-chip fleet sweep can have: a
scan step that returns its state unchanged, half of the containers left out
of the rows' means, and one container's answer altered where it is
produced, in the rows or in the region plan. (No cell exchanges data
between chips.)"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import cells, run
from repro.cluster import placement_jax
from repro.core import fleet_jax

SIZES = {"n_traces": 240}
SEED = 2**33 + 99
CELLS = [w["name"] for w in cells.load()["workloads"]]


def _run(name):
    return run.run_cell(cells.load(), name, SEED, 0.0, False, jax.devices(),
                        {}, time.perf_counter(), sizes=SIZES)


def _state_unchanged(monkeypatch):
    orig = fleet_jax._fleet_scan

    def frozen(*a, **k):
        (acc, mid, dyni), ys = orig(*a, **k)
        dyni = dyni.at[fleet_jax._I_MIGS].set(0)
        dyni = dyni.at[fleet_jax._I_SUSCNT:].set(0)
        return (jnp.zeros_like(acc), mid, dyni), ys
    monkeypatch.setattr(fleet_jax, "_fleet_scan", frozen)


_PER_CONTAINER = ("emissions_g", "energy_wh", "work_done", "work_demanded",
                  "throttled_integral", "migrations", "suspended_s",
                  "time_on_slice_s", "unmetered_g")


def _half_batch(monkeypatch):
    orig = fleet_jax.FleetSimulatorJax.run

    def half(self, *a, n_rep=1, **k):
        res = orig(self, *a, n_rep=n_rep, **k)
        n_tr = res.emissions_g.shape[0] // n_rep
        h = n_tr // 2
        for f in _PER_CONTAINER:
            arr = getattr(res, f)
            if arr is None:
                continue
            v = arr.reshape(n_rep, n_tr, *arr.shape[1:]).copy()
            v[:, h:2 * h] = v[:, :h]
            setattr(res, f, v.reshape(arr.shape))
        return res
    monkeypatch.setattr(fleet_jax.FleetSimulatorJax, "run", half)


def _answer_altered(monkeypatch):
    orig = fleet_jax.FleetSimulatorJax.run

    def altered(self, *a, n_rep=1, **k):
        res = orig(self, *a, n_rep=n_rep, **k)
        e = res.emissions_g.copy()
        e[::e.shape[0] // n_rep] *= 1.01     # one container of each target
        res.emissions_g = e
        return res
    monkeypatch.setattr(fleet_jax.FleetSimulatorJax, "run", altered)


def _region_altered(monkeypatch):
    orig = placement_jax.plan_jax

    def altered(*a, **k):
        plan = orig(*a, **k)
        R = plan.region_intensity.shape[1]
        plan.assign[100:, 0] = (plan.assign[100:, 0] + 1) % R
        return plan
    monkeypatch.setattr(placement_jax, "plan_jax", altered)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["row_rel_gap"]["value"] < 1e-12


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _region_altered])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(name)
    assert not out["correct"], out["checks"]
