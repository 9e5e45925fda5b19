"""Plain reference of the traffic layer: a synthetic user population's
requests, routed across the regions under a latency SLO and served by
autoscaled replicas (CASPER, arXiv:2403.14792), and the demand factor its
serving load puts on each region's containers.

Written from the configuration's `layers.traffic` and the rules its
modules state, with plain NumPy; it imports nothing of the program.

Arrivals, from the population's seed (one PCG64 stream, drawn in this
order): users are split over the regions by largest remainder of their
weights (ties to the lower region); region by region, each user's daily
rate is lognormal with mean `req_per_user_day` (log-mean shifted by
-sigma^2/2), and the region's rate is their sum; then the AR(1)+burst
factors of the utilisation generator, one column a region, at volatility
`cov`. A region's shape is a 1 + a cos(2 pi (hour + tz - peak hour)/24)
diurnal (a = (p - 1)/(p + 1) for peak-to-trough p, floored at 0.05)
times those factors, rescaled to mean 1; an epoch carries its share of
the day's requests.

Routing, per epoch: each source region ranks the serving regions by
carbon (lower first; SLO-infeasible ones, latency above `slo_ms`, after
all feasible ones, ties by region index). Rank by rank, each serving
region takes the sources that rank it there in source order, each up to
what is left of the region's capacity after the wants of the sources
before it; with `spill` a source goes on down its ranking, served out of
SLO where infeasible. Capacity is a fully scaled region.

Autoscaling, per epoch and region: need = ceil(load / cap1) replicas
(cap1 requests a replica-epoch), within [max(min, prev - step),
min(max, prev + step)]; the replicas serve min(load, n cap1) at
n base + (peak - base) served/cap1 watts.

Coupling: a region's containers run at demand times gain x served /
(fully scaled capacity).
"""
from __future__ import annotations

import numpy as np

from bench.gen.azure_like import ar1_burst_factors


def user_counts(p: dict) -> np.ndarray:
    w = np.asarray(p["region_weights"], dtype=np.float64)
    quota = w / w.sum() * p["n_users"]
    counts = np.floor(quota).astype(np.int64)
    short = int(p["n_users"] - counts.sum())
    if short:
        counts[np.argsort(-(quota - counts), kind="stable")[:short]] += 1
    return counts


def arrivals(t: dict, seed: int, T: int, interval_s: float) -> np.ndarray:
    """(T, R) requests arriving in each region in each epoch."""
    p = t["population"]
    rng = np.random.default_rng(seed)
    sig = p["rate_lognorm_sigma"]
    mu = np.log(max(p["req_per_user_day"], 1e-12)) - 0.5 * sig ** 2
    per_day = np.array([np.exp(rng.normal(mu, sig, int(k))).sum()
                        for k in user_counts(p)])
    R = per_day.size
    hours = np.arange(T) * interval_s / 3600.0
    local = hours[:, None] + np.asarray(p["tz_offset_h"])[None, :]
    ptt = p["peak_to_trough"]
    shape = np.maximum(1.0 + (ptt - 1.0) / (ptt + 1.0) * np.cos(
        2.0 * np.pi * (local - p["peak_hour_local"]) / 24.0), 0.05)
    noise = ar1_burst_factors(rng, T, np.full(R, max(p["cov"], 0.02)),
                              rho=p["ar1_rho"])
    factors = shape * noise
    if p["normalize"]:
        factors = factors / np.maximum(factors.mean(axis=0), 1e-12)
    return per_day[None, :] * (interval_s / 86400.0) * factors


def route(requests: np.ndarray, capacity: float, carbon: np.ndarray,
          latency: np.ndarray, routing: dict) -> dict:
    """Each epoch's routed load per serving region, what no region took,
    and what was served out of SLO, per source."""
    if routing["policy"] != "carbon":
        raise ValueError("the reference routes by carbon")
    T, R = requests.shape
    ok = latency <= routing["slo_ms"]
    n_ok = ok.sum(axis=1)
    routed = np.zeros((T, R))
    dropped = np.zeros((T, R))
    outside = np.zeros((T, R))
    for n in range(T):
        left = requests[n].tolist()
        free = [float(capacity)] * R
        ranks = [sorted(range(R), key=lambda r: (not ok[s, r], carbon[n, r]))
                 for s in range(R)]
        for k in range(R):
            for r in range(R):
                before = 0.0
                takes = []
                for s in range(R):
                    asks = ranks[s][k] == r and (routing["spill"]
                                                 or k < n_ok[s])
                    want = left[s] if asks else 0.0
                    takes.append(min(want, max(free[r] - before, 0.0)))
                    before += want
                for s in range(R):
                    left[s] -= takes[s]
                    if not ok[s, r]:
                        outside[n, s] += takes[s]
                free[r] = max(free[r] - before, 0.0)
        routed[n] = capacity - np.array(free)
        dropped[n] = left
    return {"routed": routed, "dropped": dropped, "outside": outside}


def autoscale(routed: np.ndarray, carbon: np.ndarray, rep: dict,
              interval_s: float) -> dict:
    if rep["budget_g_per_epoch"] is not None:
        raise ValueError("the reference models replicas without a budget")
    T, R = routed.shape
    cap1 = rep["throughput_rps"] * interval_s
    span = rep["peak_w"] - rep["base_w"]
    n = np.zeros((T, R))
    prev = np.full(R, float(rep["min_replicas"]))
    for t in range(T):
        lo = np.maximum(float(rep["min_replicas"]), prev - rep["max_step"])
        hi = np.minimum(float(rep["max_replicas"]), prev + rep["max_step"])
        n[t] = prev = np.minimum(np.maximum(np.ceil(routed[t] / cap1), lo),
                                 hi)
    served = np.minimum(routed, n * cap1)
    watts = n * rep["base_w"] + span * (served / cap1)
    return {"replicas": n, "served": served, "dropped": routed - served,
            "grams": watts * interval_s / 3600.0 * carbon / 1000.0}


def simulate(t: dict, seed: int, carbon: np.ndarray,
             interval_s: float) -> tuple:
    """The layer over the (T, R) carbon the router reads: its row
    readings, and the (T, R) demand factor of each region's containers."""
    T, R = carbon.shape
    requests = arrivals(t, seed, T, interval_s)
    rep = t["replicas"]
    full = rep["max_replicas"] * rep["throughput_rps"] * interval_s
    rt = route(requests, full, carbon,
               np.asarray(t["latency_ms"], dtype=np.float64), t["routing"])
    sc = autoscale(rt["routed"], carbon, rep, interval_s)
    offered = float(requests.sum())
    served = float(sc["served"].sum())
    dropped = float(rt["dropped"].sum() + sc["dropped"].sum())
    outside = float(rt["outside"].sum())
    grams = float(sc["grams"].sum())
    row = {
        "traffic_offered": offered,
        "traffic_served": served,
        "traffic_dropped": dropped,
        "traffic_slo_violations": outside,
        "traffic_violation_rate": outside / max(offered, 1e-12),
        "traffic_drop_rate": dropped / max(offered, 1e-12),
        "traffic_emissions_g": grams,
        "traffic_carbon_per_request_g": grams / max(served, 1e-12),
        "traffic_replica_epochs": float(sc["replicas"].sum()),
    }
    return row, t["demand_gain"] * sc["served"] / full
