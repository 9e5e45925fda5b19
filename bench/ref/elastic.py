"""Plain reference of the elasticity layer: K resource levels per
container, allocated each epoch by the CarbonScaler greedy under a
forecast-shaped gram budget (arXiv:2302.08681), with unserved work
deferred to later epochs.

Written from the configuration's `layers.elasticity` and the rules its
modules state, with plain NumPy; it imports nothing of the program.

Forecast ("forecast": diurnal profile plus AR(1)), causal, per column:
x^_0 = x_0; x^_t = max(0, mu_slot(t) + rho (x_{t-1} - mu_slot(t-1))),
where mu_slot is the mean of the past observations in that slot of the
day, or the running mean of all past observations where the slot has
none. Its horizon companion, the forecast mean of the coming day, is the
mean over all slots of the same per-slot means (x_0 at epoch 0).

Budget: the fleet's budget (per trace and epoch, times the traces) is
spread over the epochs in proportion to clip((day mean / max(nowcast,
1e-9))^gamma, 1/4, 4) of the forecast of the signal, which is the mean
carbon intensity the controller reads at the fleet's planned regions.

Greedy, per epoch, each container wanting w = d^ dt + backlog and seen
at forecast intensity c^: level k (1..K) serves the work
clip(w - (k - 1) cap, 0, cap), cap = unit_capacity dt, for (base + (peak -
base) work/cap) dt c^ grams. Levels up to lo = max(min_level, prev - step)
are mandatory; those in (lo, min(max(ceil(w / cap), lo), prev + step)]
are optional and are admitted in order of work per gram (free levels
first, ties in container then level order) while the mandatory grams
plus the admitted ones fit the epoch's budget. The levels serve
min(offered + backlog, level cap) of the work actually offered; the rest
is deferred. Grams are booked at c^ for what was planned (est) and at the
true intensity for what was served; an epoch whose planned grams exceed
the larger of its budget and its mandatory levels' grams (by 1e-9) is a
violation.
"""
from __future__ import annotations

import numpy as np


def diurnal_ar1(x: np.ndarray, period: int, rho: float) -> np.ndarray:
    """The causal forecast of each column of (T, C) `x`."""
    T, C = x.shape
    out = np.empty_like(x)
    slot_sum = np.zeros((period, C))
    slot_n = np.zeros(period, dtype=np.int64)
    run = np.zeros(C)
    for t in range(T):
        if t == 0:
            out[0] = x[0]
        else:
            overall = run / t
            s, sp = t % period, (t - 1) % period
            mu = slot_sum[s] / slot_n[s] if slot_n[s] else overall
            mu_p = slot_sum[sp] / slot_n[sp] if slot_n[sp] else overall
            out[t] = np.maximum(0.0, mu + rho * (x[t - 1] - mu_p))
        slot_sum[t % period] += x[t]
        slot_n[t % period] += 1
        run = run + x[t]
    return out


def day_mean_forecast(x: np.ndarray, period: int) -> np.ndarray:
    """The forecast mean of the coming day, of (T,) `x`."""
    T = x.shape[0]
    out = np.empty(T)
    slot_sum = np.zeros(period)
    slot_n = np.zeros(period, dtype=np.int64)
    run = 0.0
    for t in range(T):
        if t == 0:
            out[0] = x[0]
        else:
            out[t] = np.where(slot_n > 0, slot_sum / np.maximum(slot_n, 1),
                              run / t).mean()
        slot_sum[t % period] += x[t]
        slot_n[t % period] += 1
        run += x[t]
    return np.maximum(0.0, out)


def period_of(interval_s: float) -> int:
    return max(1, int(round(24 * 3600.0 / interval_s)))


def shaped_budget(e: dict, n_traces: int, signal: np.ndarray,
                  interval_s: float) -> np.ndarray:
    """(T,) grams each epoch may spend."""
    if e["forecast"] != "forecast" or not e["shape_budget"]:
        raise ValueError("the reference models the shaped diurnal budget")
    per_epoch = e["budget_g_per_epoch_per_trace"] * n_traces
    p = period_of(interval_s)
    now = diurnal_ar1(signal[:, None], p, e["rho"])[:, 0]
    day = day_mean_forecast(signal, p)
    share = np.clip((day / np.maximum(now, 1e-9)) ** e["shape_gamma"],
                    0.25, 4.0)
    T = signal.shape[0]
    return per_epoch * T * share / share.sum()


def _grams(e, levels, work, cap, c, interval_s):
    watts = levels * e["base_w"] + (e["peak_w"] - e["base_w"]) * (work / cap)
    return float((watts * interval_s / 3600.0 * c / 1000.0).sum())


def allocate(e: dict, want, chat, prev, budget: float, interval_s: float):
    """One epoch's levels (n,) and their mandatory floor."""
    cap = e["unit_capacity"] * interval_s
    K = e["k_levels"]
    lo = np.maximum(float(e["min_level"]), prev - e["max_step"])
    top = np.minimum(np.maximum(np.ceil(want / cap), lo),
                     np.minimum(float(K), prev + e["max_step"]))
    k = np.arange(1, K + 1, dtype=np.float64)[None, :]
    work = np.clip(want[:, None] - (k - 1.0) * cap, 0.0, cap)
    g = ((e["base_w"] + (e["peak_w"] - e["base_w"]) * (work / cap))
         * interval_s / 3600.0 * chat[:, None] / 1000.0)
    mand_g = g[k <= lo[:, None]].sum()
    opt = np.flatnonzero(((k > lo[:, None]) & (k <= top[:, None])).ravel())
    go, wo = g.ravel()[opt], work.ravel()[opt]
    free = go <= 0.0
    score = np.where(free, -np.inf, -(wo / np.where(free, 1.0, go)))
    order = np.argsort(score, kind="stable")
    took = opt[order][mand_g + np.cumsum(go[order]) <= budget]
    return lo + np.bincount(took // K, minlength=want.shape[0]), lo


def simulate(e: dict, demand: np.ndarray, c_true: np.ndarray,
             chat: np.ndarray, budget: np.ndarray, interval_s: float):
    """The layer over the (T, n) demand: the row readings and the (T, n)
    demand the fleet serves."""
    T, n = demand.shape
    dt = interval_s
    cap = e["unit_capacity"] * dt
    dhat = diurnal_ar1(demand, period_of(dt), e["rho"])
    backlog = np.zeros(n)
    prev = np.full(n, float(e["min_level"]))
    served = np.empty((T, n))
    est_g = act_g = 0.0
    viol = levels = 0
    for t in range(T):
        want = dhat[t] * dt + backlog
        lev, lo = allocate(e, want, chat[t], prev, float(budget[t]), dt)
        offered = demand[t] * dt
        srv = np.minimum(offered + backlog, lev * cap)
        backlog = backlog + offered - srv
        est = _grams(e, lev, np.minimum(want, lev * cap), cap, chat[t], dt)
        mand = _grams(e, lo, np.minimum(want, lo * cap), cap, chat[t], dt)
        est_g += est
        act_g += _grams(e, lev, srv, cap, c_true[t], dt)
        viol += est > max(float(budget[t]), mand) + 1e-9
        levels += int(lev.sum())
        served[t] = srv
        prev = lev
    offered_w = float((demand * dt).sum())
    served_w = float(served.sum())
    row = {
        "elastic_offered_work": offered_w,
        "elastic_served_work": served_w,
        "elastic_deferred_work": float(backlog.sum()),
        "elastic_served_frac": served_w / max(offered_w, 1e-12),
        "elastic_level_epochs": levels,
        "elastic_est_emissions_g": est_g,
        "elastic_emissions_g": act_g,
        "elastic_cap_violations": int(viol),
    }
    return row, served / dt
