"""Plain reference of a placed fleet sweep: the region plan, then the
Carbon Containers policy on every container of a carbon target, then the
sweep's aggregate row for that target.

Written from the rules the configuration file states (paper s3.2 and
s5.1-5.3, the repo's placement layer as its module docstring specifies
it), one epoch at a time over the containers, with plain NumPy. It imports
nothing of the program and takes nothing it made: only the generated
demand traces (T, N) and region carbon (T, R).

The rules, per 5-minute epoch:

Region plan (all N traces; a container's region serves every target):
  p_est  = base_b + (peak_b - base_b) * min(d / mult_b, 1)  baseline power
  save_r = p_est * (c_here - c_r) / 1000 * horizon_h         grams saved
  cost_r = 2 base_b * mig_s / 3600 * (c_here + c_r) / 2 / 1000
  net_r  = save_r - (1 + hysteresis) * cost_r
  A container at least `min_dwell` epochs after its last move asks for its
  best region (first of equal nets) when that net is positive and it is
  not already there. Occupancy is read at the start of the epoch; in each
  round every asking container asks for its best remaining region, and a
  region admits askers in container order while it has free slots; the
  denied strike that region and ask again next round (at most R rounds).
  A move pays cost_r grams of overhead.

Policy (energy-efficiency variant), on the container's region carbon c,
with budget w = (1 - eps) * target * 1000 / c watts:
  suspended: resume on the smallest slice with duty u_cap when its idle
    power fits the budget, else stay suspended;
  over budget on slice i (power at the demand, or idle, above w):
    idle above w: move to the next smaller slice (duty its cap, 0 if its
      idle does not fit either), or suspend on the smallest;
    else scale down to u_cap_i, and move to the next smaller slice when it
      emits less and throttles no more;
  under budget: move to the next smaller slice when it serves the 6-epoch
    peak demand at most 90% utilised and within its cap with less than
    (1 - idle_margin) of the power (only `min_dwell` epochs after the last
    move); else, when throttled, move up to the smallest larger slice that
    serves the demand within the budget; else run at min(1, u_cap_i).
A move pays the stop-and-copy downtime (Fig. 7): both slices idle for that
share of the epoch, the rest is served on the new slice. A suspended
container draws no power and serves nothing.
"""
from __future__ import annotations

import math

import numpy as np

MOVE, STAY, SUSPEND, RESUME = 0, 1, 2, 3

# The layers this reference models besides placement: none.
LAYERS = ()
# Row keys that hold counts (means of integer counts over the same
# containers): a different decision anywhere shows here as an inequality.
COUNT_KEYS = ("migrations_mean", "placement_migrations_mean")


class Servers:
    """The slice family: capacity multiples of the baseline server, with
    idle and peak power proportional to the multiple (paper s5.1.2)."""

    def __init__(self, cfg: dict):
        s = cfg["slices"]
        self.names = tuple(s["names"])
        self.mult = np.asarray(s["multiples"], dtype=np.float64)
        if np.any(np.diff(self.mult) <= 0):
            raise ValueError("slices must be listed smallest first")
        self.base = s["base_w"] * self.mult
        self.peak = s["peak_w"] * self.mult
        self.bw = np.full(len(self.mult), float(s["state_bw_gbps"]))
        self.baseline = int(s["baseline"])
        self.top = len(self.mult) - 1

    def power(self, i, u):
        """Watts of slice `i` at utilisation `u` (clipped to [0, 1])."""
        return self.base[i] + (self.peak[i] - self.base[i]) * np.clip(u, 0, 1)

    def cap_util(self, i, watts):
        """The utilisation quota that keeps slice `i` at or under `watts`."""
        u = np.minimum(1.0, (watts - self.base[i])
                       / (self.peak[i] - self.base[i]))
        return np.where(watts <= self.base[i], 0.0, u)


def downtime_s(cfg: dict, gbps):
    """Stop-and-copy seconds of a compressed migration (paper Fig. 7)."""
    m, gb = cfg["migration"], cfg["sim"]["state_gb"]
    t = ((m["suspend_base_s"] + m["suspend_per_gb_s"] * gb)
         + (m["resume_base_s"] + m["resume_per_gb_s"] * gb))
    t = t + (m["compress_per_gb_s"] + m["decompress_per_gb_s"]) * gb
    return t + (gb / m["compression_ratio"]) / gbps


def capacity(cfg: dict) -> int:
    """Containers each region holds: a share of the traces."""
    return int(math.ceil(cfg["capacity"]["share"] * int(cfg["n_traces"])))


def region_plan(cfg: dict, demand: np.ndarray, regions: np.ndarray,
                cap: int = None) -> dict:
    """The region of every container in every epoch (after that epoch's
    moves), with the moves and overhead grams per container."""
    T, N = demand.shape
    R = regions.shape[1]
    cap = capacity(cfg) if cap is None else cap
    if N > cap * R:
        raise ValueError(f"{N} containers do not fit {R} x {cap} slots")
    sv = Servers(cfg)
    p = cfg["placement"]
    b = sv.baseline
    dt = cfg["sim"]["interval_s"]
    cost0 = 2.0 * sv.base[b] * downtime_s(cfg, p["link_gbps"]) / 3600.0
    horizon_h = p["horizon_intervals"] * dt / 3600.0
    keep = 1.0 + p["hysteresis"]

    # every region has the same capacity: fill them round-robin
    where = np.arange(N) % R
    occ = np.bincount(where, minlength=R)
    since = np.full(N, 10 ** 9)              # no move yet: free to move
    moves = np.zeros(N, dtype=np.int64)
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
        for _ in range(R):
            best = np.argmax(net, axis=1)
            asks = ((since >= p["min_dwell"]) & (dest < 0) & (best != where)
                    & (net[everyone, best] > 0.0))
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
        m = np.flatnonzero(dest >= 0)
        overhead[m] += cost0 * (0.5 * (c[where[m]] + c[dest[m]])) / 1000.0
        moves[m] += 1
        occ += np.bincount(dest[m], minlength=R) - np.bincount(where[m],
                                                                minlength=R)
        where[m] = dest[m]
        since += 1
        since[m] = 0
        out[n] = where
    return {"assign": out, "migrations": moves, "overhead_g": overhead}


def _smallest_larger_fit(sv: Servers, i, d, w):
    """The smallest larger slice that serves demand `d` within `w` watts,
    climbing one slice at a time and giving up at the first that does not
    fit; -1 where none does."""
    found = np.full(i.shape, -1)
    k = i + 1
    live = k <= sv.top
    while live.any():
        kk = np.minimum(k, sv.top)
        fits = sv.power(kk, np.minimum(d / sv.mult[kk], 1.0)) <= w
        done = live & fits & ((d <= sv.mult[kk]) | (kk == sv.top))
        found[done] = kk[done]
        live = live & fits & ~done
        k = k + 1
    return found


def decide(cfg: dict, sv: Servers, slc, suspended, since, d, peak, c,
           target):
    """The policy's action, duty and slice for every container."""
    pol = cfg["policy"]
    eps = cfg["sim"]["epsilon"]
    w = (1.0 - eps) * target * 1000.0 / c
    n = d.shape[0]
    act = np.full(n, STAY)
    duty = np.zeros(n)
    dest = slc.copy()
    has_j = slc > 0
    j = np.maximum(slc - 1, 0)
    u_cap_i = sv.cap_util(slc, w)
    u_cap_j = sv.cap_util(j, w)
    u_need = np.minimum(d / sv.mult[slc], 1.0)
    p_need = sv.power(slc, u_need)
    idle_over = sv.base[slc] > w
    over = ~suspended & ((p_need > w) | idle_over)
    under = ~suspended & ~over

    # suspended: resume on the smallest slice when its idle power fits
    wake = suspended & (sv.base[0] <= w) & (sv.cap_util(0, w) > 0.0)
    act[suspended] = SUSPEND
    act[wake], duty[wake], dest[wake] = RESUME, sv.cap_util(0, w)[wake], 0

    # over budget, even idle: step down one slice, or suspend on the smallest
    hard = over & (idle_over | (u_cap_i <= 0.0))
    down = hard & has_j
    act[down], dest[down] = MOVE, j[down]
    duty[down] = np.where(sv.base[j] <= w, np.maximum(u_cap_j, 0.0), 0.0)[down]
    act[hard & ~has_j] = SUSPEND

    # over budget: scale down to the cap, or step down if that emits less
    soft = over & ~hard
    thr_i = np.maximum(0.0, d - sv.mult[slc] * u_cap_i)
    rate_i = sv.power(slc, np.minimum(u_cap_i, u_need)) * c / 1000.0
    u_j = np.minimum(np.minimum(d / sv.mult[j], u_cap_j), 1.0)
    thr_j = np.maximum(0.0, d - sv.mult[j] * u_j)
    rate_j = sv.power(j, u_j) * c / 1000.0
    smaller = soft & has_j & (rate_j < rate_i) & (thr_j <= thr_i + 1e-12)
    duty[soft] = u_cap_i[soft]
    act[smaller], dest[smaller] = MOVE, j[smaller]
    duty[smaller] = np.maximum(u_cap_j, 0.0)[smaller]

    # under budget: a smaller slice that serves the recent peak for less,
    # else a larger one when throttled, else run under the cap
    u_pk = peak / sv.mult[j]
    thrifty = (under & has_j & (since >= pol["min_dwell"])
               & (u_pk <= np.minimum(u_cap_j, 0.9))
               & (sv.power(j, np.minimum(u_pk, 1.0))
                  < (1.0 - pol["idle_margin"]) * p_need))
    duty[under] = np.minimum(1.0, u_cap_i)[under]
    act[thrifty], dest[thrifty] = MOVE, j[thrifty]
    duty[thrifty] = np.minimum(1.0, np.maximum(u_cap_j, 0.0))[thrifty]
    throttled = under & ~thrifty & (d > sv.mult[slc] * np.minimum(u_cap_i,
                                                                  1.0))
    if throttled.any():
        up = np.full(n, -1)
        up[throttled] = _smallest_larger_fit(sv, slc[throttled],
                                             d[throttled], w[throttled])
        grow = up >= 0
        act[grow], dest[grow], duty[grow] = MOVE, up[grow], 1.0
    return act, duty, dest


def target_row(cfg: dict, demand: np.ndarray, carbon: np.ndarray,
               target: float) -> dict:
    """One target's containers (one per trace) through every epoch on the
    carbon of their planned regions (T, N): the sweep's row for it."""
    if not cfg["sim"]["suspend_releases_slice"]:
        raise ValueError("the reference models a suspend that releases "
                         "the slice")
    sv = Servers(cfg)
    T, N = demand.shape
    dt = cfg["sim"]["interval_s"]
    slc = np.full(N, sv.baseline)
    duty = np.ones(N)
    suspended = np.zeros(N, dtype=bool)
    since = np.full(N, 10 ** 9)
    grams = np.zeros(N)
    throttled = np.zeros(N)
    susp_s = np.zeros(N)
    moves = np.zeros(N, dtype=np.int64)
    on_slice = np.zeros((N, len(sv.mult) + 1))
    everyone = np.arange(N)
    for n in range(T):
        d = demand[n]
        c = carbon[n]
        if np.any(c <= 0.0):
            raise ValueError("the reference needs positive carbon intensity")
        peak = demand[max(0, n - 5):n + 1].max(axis=0)
        act, new_duty, dest = decide(cfg, sv, slc, suspended, since, d, peak,
                                     c, target)

        go = act == MOVE
        src = slc.copy()
        gone = downtime_s(cfg, np.maximum(sv.bw[src], sv.bw[dest]))
        if np.any(gone[go] >= dt):
            raise ValueError("the reference models migrations shorter "
                             "than an epoch")
        down = np.minimum(gone, dt) / dt
        moves[go] += 1
        slc = np.where(go | (act == RESUME), dest, slc)
        suspended = act == SUSPEND
        duty = np.where(suspended, duty, new_duty)

        served_cap = sv.mult[slc] * np.clip(duty, 0.0, 1.0)
        served = np.minimum(d, served_cap)
        watts = sv.power(slc, served / sv.mult[slc])
        watts = np.where(go, down * (sv.base[src] + sv.base[dest])
                         + (1 - down) * watts, watts)
        served = np.where(go, (1 - down) * served, served)
        watts[suspended] = 0.0
        served[suspended] = 0.0

        grams += watts * c / 1000.0 * dt / 3600.0
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
    }


def sweep(cfg: dict, inputs: dict, targets, cap: int = None):
    """The reference's rows for `targets` and its region plan.

    Containers of different targets never interact, and the plan depends
    on the traces alone, so the rows of a subset of the targets are the
    whole sweep's rows for them."""
    demand, regions = inputs["traces"], inputs["regions"]
    plan = region_plan(cfg, demand, regions, cap)
    T = demand.shape[0]
    carbon = regions[np.arange(T)[:, None], plan["assign"]]
    rows = []
    for target in targets:
        row = target_row(cfg, demand, carbon, target)
        row["placement_migrations_mean"] = float(plan["migrations"].mean())
        row["placement_overhead_g_mean"] = float(plan["overhead_g"].mean())
        rows.append(row)
    return rows, plan
