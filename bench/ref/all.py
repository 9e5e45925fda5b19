"""Plain reference of the all-layer fleet sweep: faults, grid events,
placement, traffic, energy, elasticity, then the Carbon Containers policy
on every container of a target, and the sweep's row for it.

Written from the rules the configuration states, with plain NumPy, on the
layers' references (`bench/ref/{faults,traffic,energy,elastic}.py`) and
the placed reference's servers, policy and migration model
(`bench.ref.placed`); it imports nothing of the program. Its inputs are
the generated demand traces (T, n) and region carbon (T, R), and the
run's stream seeds.

The order, as the configuration states it:
1. grid events: shocks multiply the region carbon; that is the true
   intensity, which every layer bills at;
2. faults: the ladder turns the true intensity into the observed one,
   which every controller reads (planner, router, elastic budget and
   forecasts, the policy's power budget);
3. placement: the placed reference's plan, on the observed intensity and
   the traces, where an attempted move fails per the failure mask: it
   pays its stop-and-copy grams but the container stays put, does not
   count as a move, and waits min(base 2^(k - 1), cap) epochs after its
   k-th failure in a row before it may ask again (a move resets the
   streak);
4. traffic on the observed intensity: each container's demand is scaled
   by its region's factor;
5. energy on the true intensity and that demand: demand is scaled by the
   region's cap fraction, billing moves to the effective intensity, and
   the controller reads that intensity times its region's observed/true
   ratio (1 where the true intensity is 0);
6. elasticity: served demand in place of demand, budgeted on the mean
   observed intensity at the planned regions, forecasting the observed
   effective intensity;
7. the policy on the served demand, deciding on the observed effective
   intensity and billed at the true one; a policy power budget with a
   read intensity of 0 is unbounded. Grams of epochs the meter missed
   are also summed as unmetered.
"""
from __future__ import annotations

import numpy as np

from bench.ref import elastic, energy, faults, placed, traffic

LAYERS = ("traffic", "elasticity", "energy", "faults")
# Counts, and shares of counts over the same containers or samples;
# traffic_slo_violations is requests, a float, and compared by its gap.
COUNT_KEYS = placed.COUNT_KEYS + (
    "traffic_replica_epochs", "elastic_level_epochs",
    "elastic_cap_violations", "energy_outage_epochs",
    "energy_cap_violations", "energy_soc_violations", "fault_stale_frac",
    "fault_hold_frac", "fault_prior_frac", "fault_floor_frac",
    "fault_max_age", "fault_failed_migrations_mean")


def region_plan(cfg: dict, demand: np.ndarray, regions: np.ndarray,
                fail: np.ndarray, cap: int = None) -> dict:
    """The placed reference's plan (`placed.region_plan`) with failing
    moves: the region of every container in every epoch, its moves,
    failed moves and stop-and-copy grams."""
    T, N = demand.shape
    R = regions.shape[1]
    cap = placed.capacity(cfg) if cap is None else cap
    if N > cap * R:
        raise ValueError(f"{N} containers do not fit {R} x {cap} slots")
    sv = placed.Servers(cfg)
    p = cfg["placement"]
    m = cfg["layers"]["faults"]["migration"]
    exp_cap = int(m["backoff_cap"]).bit_length()
    b = sv.baseline
    dt = cfg["sim"]["interval_s"]
    cost0 = 2.0 * sv.base[b] * placed.downtime_s(cfg, p["link_gbps"]) / 3600.0
    horizon_h = p["horizon_intervals"] * dt / 3600.0
    keep = 1.0 + p["hysteresis"]

    where = np.arange(N) % R
    occ = np.bincount(where, minlength=R)
    since = np.full(N, 10 ** 9)
    moves = np.zeros(N, dtype=np.int64)
    failed = np.zeros(N, dtype=np.int64)
    streak = np.zeros(N, dtype=np.int64)
    retry = np.zeros(N, dtype=np.int64)
    overhead = np.zeros(N)
    out = np.empty((T, N), dtype=np.int64)
    everyone = np.arange(N)
    for n in range(T):
        c = regions[n]
        here = c[where][:, None]
        p_est = sv.base[b] + (sv.peak[b] - sv.base[b]) * np.minimum(
            demand[n] / sv.mult[b], 1.0)
        save = p_est[:, None] * (here - c[None, :]) / 1000.0 * horizon_h
        cost = cost0 * (0.5 * (here + c[None, :])) / 1000.0
        net = save - keep * cost
        free = cap - occ
        dest = np.full(N, -1)
        may = (since >= p["min_dwell"]) & (n >= retry)
        for _ in range(R):
            best = np.argmax(net, axis=1)
            asks = may & (dest < 0) & (best != where) & (net[everyone,
                                                             best] > 0.0)
            if not asks.any():
                break
            denied = False
            for r in range(R):
                who = np.flatnonzero(asks & (best == r))
                k = min(max(int(free[r]), 0), who.size)
                dest[who[:k]] = r
                free[r] -= k
                if k < who.size:
                    net[who[k:], r] = -np.inf
                    denied = True
            if not denied:
                break
        tried = np.flatnonzero(dest >= 0)
        overhead[tried] += cost0 * (0.5 * (c[where[tried]]
                                           + c[dest[tried]])) / 1000.0
        lost = tried[fail[n, tried]]
        went = tried[~fail[n, tried]]
        failed[lost] += 1
        streak[lost] += 1
        streak[went] = 0
        # past the cap's bit length the doubling only overflows
        retry[lost] = n + 1 + np.minimum(
            m["backoff_base"] * 2 ** np.minimum(streak[lost] - 1, exp_cap),
            m["backoff_cap"])
        moves[went] += 1
        occ += (np.bincount(dest[went], minlength=R)
                - np.bincount(where[went], minlength=R))
        where[went] = dest[went]
        since += 1
        since[went] = 0
        out[n] = where
    return {"assign": out, "migrations": moves, "failed": failed,
            "overhead_g": overhead}


def target_row(cfg: dict, demand: np.ndarray, c_bill: np.ndarray,
               c_read: np.ndarray, gap: np.ndarray, target: float) -> dict:
    """`placed.target_row` deciding on the read intensity `c_read` and
    billed at `c_bill` (both (T, n), either may be 0), with the grams of
    the meter's gap epochs summed as unmetered."""
    if not cfg["sim"]["suspend_releases_slice"]:
        raise ValueError("the reference models a suspend that releases "
                         "the slice")
    sv = placed.Servers(cfg)
    T, N = demand.shape
    dt = cfg["sim"]["interval_s"]
    slc = np.full(N, sv.baseline)
    duty = np.ones(N)
    suspended = np.zeros(N, dtype=bool)
    since = np.full(N, 10 ** 9)
    grams = np.zeros(N)
    unmetered = np.zeros(N)
    throttled = np.zeros(N)
    susp_s = np.zeros(N)
    moves = np.zeros(N, dtype=np.int64)
    on_slice = np.zeros((N, len(sv.mult) + 1))
    everyone = np.arange(N)
    for n in range(T):
        d = demand[n]
        peak = demand[max(0, n - 5):n + 1].max(axis=0)
        with np.errstate(divide="ignore"):
            act, new_duty, dest = placed.decide(cfg, sv, slc, suspended,
                                                since, d, peak, c_read[n],
                                                target)
        go = act == placed.MOVE
        src = slc.copy()
        gone = placed.downtime_s(cfg, np.maximum(sv.bw[src], sv.bw[dest]))
        if np.any(gone[go] >= dt):
            raise ValueError("the reference models migrations shorter "
                             "than an epoch")
        down = np.minimum(gone, dt) / dt
        moves[go] += 1
        slc = np.where(go | (act == placed.RESUME), dest, slc)
        suspended = act == placed.SUSPEND
        duty = np.where(suspended, duty, new_duty)

        served = np.minimum(d, sv.mult[slc] * np.clip(duty, 0.0, 1.0))
        watts = sv.power(slc, served / sv.mult[slc])
        watts = np.where(go, down * (sv.base[src] + sv.base[dest])
                         + (1 - down) * watts, watts)
        served = np.where(go, (1 - down) * served, served)
        watts[suspended] = 0.0
        served[suspended] = 0.0

        g = watts * c_bill[n] / 1000.0 * dt / 3600.0
        grams += g
        unmetered += g * gap[n]
        throttled += np.maximum(0.0, d - served) * dt
        susp_s[suspended] += dt
        on_slice[everyone, np.where(suspended, len(sv.mult), slc)] += dt
        since = np.where(go, 0, since + 1)

    elapsed = T * dt
    rate = grams / (elapsed / 3600.0)
    thr_pct = 100.0 * throttled / elapsed / sv.mult[sv.baseline]
    shares = (on_slice / elapsed).mean(axis=0)
    return {
        "policy": cfg["policy"]["name"], "target": float(target),
        "carbon_rate_mean": float(rate.mean()),
        "carbon_rate_std": float(rate.std()),
        "throttle_mean": float(thr_pct.mean()),
        "throttle_std": float(thr_pct.std()),
        "migrations_mean": float(moves.mean()),
        "suspended_frac_mean": float((susp_s / elapsed).mean()),
        "time_on_slice": {k: float(v) for k, v in
                          zip(sv.names + ("suspended",), shares) if v != 0.0},
        "fault_unmetered_g_mean": float(unmetered.mean()),
    }


def layers_sweep(cfg: dict, inputs: dict, targets, on=LAYERS,
                 cap: int = None):
    """The rows of `targets` and the region plan, with only the layers in
    `on` (the tests turn one off) and `cap` containers a region (the
    configuration's capacity where None; the control breaks it)."""
    L = cfg["layers"]
    seeds = inputs["seeds"]
    demand, carbon = inputs["traces"], inputs["regions"]
    T, n = demand.shape
    R = carbon.shape[1]
    dt = cfg["sim"]["interval_s"]
    rows_t = np.arange(T)[:, None]
    up = None
    if "energy" in on:
        shock, up = energy.events(L["energy"], T, R)
        carbon = carbon * shock
    read = carbon
    extra = {}
    gap = np.zeros(T)
    fail = np.zeros((T, n), dtype=bool)
    if "faults" in on:
        f = L["faults"]
        sig = faults.observe(f, seeds["faults"], carbon, dt)
        read = sig["observed"]
        extra.update(faults.summary(sig))
        gap = faults.meter_gaps(f, seeds["faults"], T)
        fail = faults.migration_failures(f, seeds["faults"], T, n)
    plan = region_plan(cfg, demand, read, fail, cap)
    assign = plan["assign"]
    if "faults" in on:
        extra["fault_failed_migrations_mean"] = float(plan["failed"].mean())
    if "traffic" in on:
        row, mod = traffic.simulate(L["traffic"], seeds["population"], read,
                                    dt)
        extra.update(row)
        demand = demand * mod[rows_t, assign]
    bill, read_eff = carbon, read
    if "energy" in on:
        row, cap_frac, c_eff = energy.simulate(
            L["energy"], cfg, seeds["energy"], demand, assign, carbon, up, dt)
        extra.update(row)
        demand = demand * cap_frac[rows_t, assign]
        bill = c_eff
        read_eff = c_eff * np.where(
            carbon > 0.0, read / np.where(carbon > 0.0, carbon, 1.0), 1.0)
    bill_cols = bill[rows_t, assign]
    if "elasticity" in on:
        e = L["elasticity"]
        budget = elastic.shaped_budget(e, n, read[rows_t, assign].mean(axis=1),
                                       dt)
        chat = elastic.diurnal_ar1(read_eff, elastic.period_of(dt),
                                   e["rho"])[rows_t, assign]
        row, demand = elastic.simulate(e, demand, bill_cols, chat, budget, dt)
        extra.update(row)
    read_cols = read_eff[rows_t, assign]
    rows = []
    for target in targets:
        row = target_row(cfg, demand, bill_cols, read_cols, gap, target)
        if "faults" not in on:
            del row["fault_unmetered_g_mean"]
        row["placement_migrations_mean"] = float(plan["migrations"].mean())
        row["placement_overhead_g_mean"] = float(plan["overhead_g"].mean())
        row.update(extra)
        rows.append(row)
    return rows, plan


def sweep(cfg: dict, inputs: dict, targets):
    """The reference's rows for `targets` and its region plan. Containers
    of different targets never interact, and every layer runs on the
    traces alone, so the rows of a subset of the targets are the whole
    sweep's rows for them."""
    return layers_sweep(cfg, inputs, targets)
