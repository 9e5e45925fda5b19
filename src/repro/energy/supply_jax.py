"""JAX supply step: the virtual energy system as one pure scan step.

`energy_step` is `repro.energy.supply.supply_step_np` term for term on
(R,)-shaped jnp arrays with a static `EnergySpec` — small enough to
fold straight into the fleet backend's `lax.scan` epoch step
(`repro.core.fleet_jax._fleet_scan`), which is how
`sweep_population(..., backend="jax", energy=...)` keeps the N=1M
placed sweep free of (T, N) intermediates: the scan carries only the
(R,) battery state-of-charge extra, the per-epoch solar/outage rows
ride in xs, and the virtual-cap and effective-intensity signals are
R-way selects over (R,) rows.

`simulate_supply_jax` scans the same step standalone and returns the
usual `SupplyResult` — parity with the NumPy ledger is pinned <= 1e-9
by tests/test_energy.py.
"""
from __future__ import annotations

import numpy as np

from repro.energy.supply import SOC_SNAP_WH, EnergySpec, SupplyResult

import jax
import jax.numpy as jnp
from jax import lax


def energy_step(spec: EnergySpec, soc, load, solar, grid_c, up):
    """One supply epoch on (R,) jnp arrays; mirrors `supply_step_np`
    (keep the two in lockstep — the cross-backend sweep parity tests
    pin them through the fleet scan). Pure; trace-safe inside any
    surrounding scan."""
    use_solar = jnp.minimum(load, solar)
    surplus = solar - use_solar
    head_w = (spec.cap_wh - soc) * (3600.0 / spec.dt) / spec.eta_c
    charge = jnp.maximum(
        jnp.minimum(jnp.minimum(surplus, spec.max_charge_w), head_w), 0.0)
    deficit = load - use_solar
    avail_w = soc * (3600.0 / spec.dt)
    discharge = jnp.maximum(
        jnp.minimum(jnp.minimum(deficit, spec.max_discharge_w), avail_w),
        0.0)
    grid = (deficit - discharge) * up
    supplied = use_solar + discharge + grid
    soc1 = soc + (charge * spec.eta_c - discharge) * (spec.dt / 3600.0)
    # drained-battery snap (see supply.SOC_SNAP_WH): without it, XLA's
    # FMA contraction of the drain epoch leaves a ~1e-13 Wh residue
    # whose femto-watt discharge flips the supplied>0 branch of c_eff
    # during outages — a last-bit difference billed as a 100% change
    soc1 = jnp.where(soc1 < SOC_SNAP_WH, 0.0, soc1)
    load_pos = load > 0.0
    cap_frac = jnp.where(
        load_pos,
        jnp.minimum(supplied / jnp.where(load_pos, load, 1.0), 1.0), 1.0)
    sup_pos = supplied > 0.0
    c_eff = grid_c * jnp.where(
        sup_pos, grid / jnp.where(sup_pos, supplied, 1.0), 1.0)
    return soc1, (use_solar, charge, discharge, grid, supplied, cap_frac,
                  c_eff)


def simulate_supply_jax(load, solar, grid_c, grid_up,
                        spec: EnergySpec) -> SupplyResult:
    """Standalone scan of `energy_step` over all T epochs (float64)."""
    load = np.asarray(load, dtype=np.float64)
    solar = np.asarray(solar, dtype=np.float64)
    grid_c = np.asarray(grid_c, dtype=np.float64)
    grid_up = np.asarray(grid_up, dtype=np.float64)
    T, R = load.shape

    def step(soc, x):
        soc1, outs = energy_step(spec, soc, *x)
        return soc1, outs + (soc1,)

    with jax.enable_x64(True):
        soc0 = jnp.full(R, spec.soc0_wh, dtype=jnp.float64)
        _, ys = jax.jit(lambda xs: lax.scan(step, soc0, xs))(
            (jnp.asarray(load), jnp.asarray(solar), jnp.asarray(grid_c),
             jnp.asarray(grid_up)))
        (solar_used, charge, discharge, grid, supplied, cap_frac, c_eff,
         soc_tr) = (np.asarray(y) for y in ys)
    return SupplyResult(load=load, solar_gen=solar, solar_used=solar_used,
                        charge=charge, discharge=discharge, grid=grid,
                        supplied=supplied, cap_frac=cap_frac, c_eff=c_eff,
                        soc=soc_tr, grid_up=grid_up, spec=spec)
