"""Plain reference of the energy layer: grid events, and each region's
virtual supply of solar, battery and grid (Ecovisor, arXiv:2210.04951).

Written from the configuration's `layers.energy` and the rules its
module states, with plain NumPy; it imports nothing of the program.

Events: an outage ((region or -1 for all, start, n)) forces the grid draw
to zero over [start, start + n); a shock ((region or -1, start, n,
factor)) multiplies the grid's carbon intensity there. The shocked
intensity is the true one every layer sees or bills.

Sizes scale with the fleet: each region has the solar peak, battery
capacity and rates of `n_traces / R` containers. Solar, per region: a
half-sine arc between sunrise and sunset of the local hour (time zone
added), times a weather factor clip(0.85 + x, 0, 1) where x is an AR(1)
(x <- rho x + sigma z, one normal per region each epoch, region order,
PCG64 from the seed).

The supply, per region and epoch, on the fleet's flexible load (the
baseline slice's (peak - base)/multiple watts per unit of demand, summed
over the region's containers): solar first; its surplus charges the
battery up to the charge rate and the headroom ((capacity - soc) per
epoch / charge efficiency); the deficit discharges up to the discharge
rate and the charge held; the grid takes the rest, none in an outage.
soc' = soc + (charge x efficiency - discharge) dt, set to 0 below
`soc_snap_wh`. Delivered = solar used + discharge + grid. The demand
side reads two signals: the cap fraction min(delivered / load, 1) (1
without load), by which the region's demand is scaled, and the effective
intensity grid intensity x grid / delivered (the grid intensity when
nothing is delivered).
"""
from __future__ import annotations

import numpy as np


def _regions(r: int, R: int):
    return range(R) if r < 0 else (r,)


def events(e: dict, T: int, R: int):
    """(shock factor (T, R), grid up (T, R) in {0, 1})."""
    ev = e["events"]
    if ev["n_random_outages"] or ev["n_random_shocks"]:
        raise ValueError("the reference models scheduled events only")
    mult = np.ones((T, R))
    up = np.ones((T, R))
    for r, start, n in ev["outages"]:
        for rr in _regions(r, R):
            up[max(0, start):min(T, start + n), rr] = 0.0
    for r, start, n, factor in ev["shocks"]:
        for rr in _regions(r, R):
            mult[max(0, start):min(T, start + n), rr] *= factor
    return mult, up


def sizes(e: dict, cfg: dict, R: int) -> dict:
    per_r = float(cfg["n_traces"]) / float(R)
    b = e["battery"]
    s = cfg["slices"]
    i = s["baseline"]
    mult = s["multiples"][i]
    return {
        "cap_wh": b["capacity_wh_per_container"] * per_r,
        "charge_w": b["max_charge_w_per_container"] * per_r,
        "discharge_w": b["max_discharge_w_per_container"] * per_r,
        "eta": float(b["eta_charge"]),
        "soc0_wh": b["capacity_wh_per_container"] * per_r * b["soc0_frac"],
        "w_per_unit": (s["peak_w"] * mult - s["base_w"] * mult) / mult,
        "solar_peak_w": e["solar"]["peak_w_per_container"] * per_r,
        "snap_wh": e["soc_snap_wh"],
    }


def solar(e: dict, seed: int, T: int, R: int, interval_s: float,
          peak_w: float) -> np.ndarray:
    """(T, R) solar generation in watts."""
    s = e["solar"]
    h = np.mod((np.arange(T) * interval_s / 3600.0)[:, None]
               + np.asarray(s["tz_offset_h"], dtype=np.float64)[None, :],
               24.0)
    up, down = s["sunrise_h"], s["sunset_h"]
    arc = np.where((h >= up) & (h <= down),
                   np.maximum(np.sin(np.pi * (h - up) / (down - up)), 0.0),
                   0.0)
    rng = np.random.default_rng(seed)
    x = np.zeros(R)
    weather = np.empty((T, R))
    for t in range(T):
        x = s["weather_rho"] * x + s["weather_sigma"] * rng.standard_normal(R)
        weather[t] = np.clip(0.85 + x, 0.0, 1.0)
    return peak_w * arc * weather


def supply(sz: dict, load: np.ndarray, sun: np.ndarray, grid_c: np.ndarray,
           up: np.ndarray, interval_s: float) -> dict:
    """The (T, R) ledger of the supply, one region and epoch at a time."""
    T, R = load.shape
    per_h = 3600.0 / interval_s
    keys = ("solar", "charge", "discharge", "grid", "delivered", "cap",
            "c_eff", "soc")
    out = {k: np.empty((T, R)) for k in keys}
    for r in range(R):
        soc = sz["soc0_wh"]
        for t in range(T):
            ld, sl = float(load[t, r]), float(sun[t, r])
            used = min(ld, sl)
            charge = max(min(sl - used, sz["charge_w"],
                             (sz["cap_wh"] - soc) * per_h / sz["eta"]), 0.0)
            short = ld - used
            dis = max(min(short, sz["discharge_w"], soc * per_h), 0.0)
            grid = (short - dis) * float(up[t, r])
            got = used + dis + grid
            soc = soc + (charge * sz["eta"] - dis) * (interval_s / 3600.0)
            if soc < sz["snap_wh"]:
                soc = 0.0
            cap = min(got / ld, 1.0) if ld > 0.0 else 1.0
            c_eff = float(grid_c[t, r]) * (grid / got if got > 0.0 else 1.0)
            for k, v in zip(keys, (used, charge, dis, grid, got, cap, c_eff,
                                   soc)):
                out[k][t, r] = v
    out.update(load=load, up=up)
    return out


def summary(led: dict, sz: dict, interval_s: float) -> dict:
    """The rows' `energy_*` readings of the ledger."""
    wh = interval_s / 3600.0
    got = max(float(led["delivered"].sum()) * wh, 1e-12)
    load_wh = max(float(led["load"].sum()) * wh, 1e-12)
    err = led["solar"] + led["discharge"] + led["grid"] - led["delivered"]
    scale = max(float(led["load"].max(initial=0.0)), 1.0)
    tol = 1e-9 * max(sz["cap_wh"], 1.0)
    return {
        "energy_solar_wh": float(led["solar"].sum()) * wh,
        "energy_battery_wh": float(led["discharge"].sum()) * wh,
        "energy_grid_wh": float(led["grid"].sum()) * wh,
        "energy_supplied_wh": float(led["delivered"].sum()) * wh,
        "energy_unmet_frac": float((led["load"] - led["delivered"]).sum())
        * wh / load_wh,
        "energy_solar_frac": float(led["solar"].sum()) * wh / got,
        "energy_grid_frac": float(led["grid"].sum()) * wh / got,
        "energy_cap_frac_min": float(led["cap"].min()),
        "energy_outage_epochs": int(np.sum(led["up"] <= 0.0)),
        "energy_conservation_max_err_w": float(np.abs(err).max()),
        "energy_cap_violations": int(np.sum(
            led["load"] * led["cap"] > led["delivered"] + 1e-9 * scale)),
        "energy_soc_violations": int(np.sum((led["soc"] < -tol)
                                            | (led["soc"] > sz["cap_wh"]
                                               + tol))),
    }


def simulate(e: dict, cfg: dict, seed: int, demand: np.ndarray,
             assign: np.ndarray, grid_c: np.ndarray, up: np.ndarray,
             interval_s: float):
    """The supply over the fleet's (T, n) demand in its planned regions:
    the row readings and the (T, R) cap fraction and effective
    intensity."""
    T, R = grid_c.shape
    sz = sizes(e, cfg, R)
    load = np.stack([np.where(assign == r, demand, 0.0).sum(axis=1)
                     for r in range(R)], axis=1) * sz["w_per_unit"]
    sun = solar(e, seed, T, R, interval_s, sz["solar_peak_w"])
    led = supply(sz, load, sun, grid_c, up, interval_s)
    return summary(led, sz, interval_s), led["cap"], led["c_eff"]
