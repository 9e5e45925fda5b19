"""JAX traffic step: routing + autoscaling as one pure scan step.

`traffic_step` is the per-epoch routing + autoscaling update as a pure
function on (R,)-shaped arrays with a static `TrafficSpec` — small
enough to fold straight into the fleet backend's `lax.scan` epoch step
(`repro.core.fleet_jax._fleet_scan`), which is how
`sweep_population(..., backend="jax", traffic=...)` keeps the N=1M
placed sweep free of (T, N) intermediates: the scan carries only the
(R,) replica vector extra, and each epoch's demand modulation is an
R-way select over the epoch's (R,) mod row.

`simulate_traffic_jax` scans the same step standalone and returns the
usual `TrafficResult` — parity with the NumPy pipeline is pinned <=1e-6
by tests/test_traffic_jax.py (replica counts match exactly). The
arithmetic mirrors `routing.route` / `autoscale.autoscale` term for
term; the only float drift is XLA's `cumsum`/reduction association.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from repro.traffic.sim import TrafficConfig, TrafficResult

import jax
import jax.numpy as jnp
from jax import lax

_BIG = 1e9


class TrafficSpec(NamedTuple):
    """Hashable static spec for `traffic_step` (jit static arg)."""
    feas: tuple            # R rows of R bools (SLO feasibility)
    n_feas: tuple          # feasible-region count per source
    lat: tuple             # R rows of R floats
    policy: str
    spill: bool
    thru: float
    base_w: float
    peak_w: float
    kmax: int
    min_rep: int
    max_step: int
    budget: Optional[float]
    gain: float
    dt: float
    R: int

    @classmethod
    def from_config(cls, cfg: TrafficConfig,
                    interval_s: float) -> "TrafficSpec":
        lat = cfg.latency_matrix()
        feas = lat <= cfg.routing.slo_ms
        rc = cfg.replicas
        return cls(
            feas=tuple(tuple(bool(x) for x in row) for row in feas),
            n_feas=tuple(int(x) for x in feas.sum(axis=1)),
            lat=tuple(tuple(float(x) for x in row) for row in lat),
            policy=cfg.routing.policy, spill=bool(cfg.routing.spill),
            thru=float(rc.throughput_rps), base_w=float(rc.base_w),
            peak_w=float(rc.peak_w), kmax=int(rc.max_replicas),
            min_rep=int(rc.min_replicas), max_step=int(rc.max_step),
            budget=(None if rc.budget_g_per_epoch is None
                    else float(rc.budget_g_per_epoch)),
            gain=float(cfg.demand_gain), dt=float(interval_s),
            R=int(cfg.population.n_regions))

    @property
    def cap1(self) -> float:
        return self.thru * self.dt

    @property
    def max_capacity(self) -> float:
        return self.kmax * self.cap1


def traffic_step(spec: TrafficSpec, rep0, req_row, c_row):
    """One epoch: route `req_row` by the carbon row, autoscale replicas.

    Returns ``(rep1, (mod, routed, served, drop_route, drop_cap, viol,
    emis))`` — all (R,) f64 except the carry `rep1`. Pure; trace-safe
    inside any surrounding scan.
    """
    R = spec.R
    feas = np.asarray(spec.feas, dtype=bool)
    offs = np.where(feas, 0.0, _BIG)                   # static (R, R)
    lat = np.asarray(spec.lat, dtype=np.float64)
    cap1 = spec.cap1
    cap = spec.max_capacity

    # ---- routing: greedy water-filling in preference-rank rounds ----
    if spec.policy == "carbon":
        score = c_row[None, :] + offs
    else:
        score = jnp.asarray(lat + offs)
    pref = jnp.argsort(score, axis=1)                  # stable by default
    remaining = req_row
    avail = jnp.full(R, cap, dtype=jnp.float64)
    viol = jnp.zeros(R, dtype=jnp.float64)
    for k in range(R):
        choice = pref[:, k]
        if spec.spill:
            requesting = np.ones(R, dtype=bool)
        else:
            requesting = np.array([k < spec.n_feas[s] for s in range(R)])
        for r in range(R):
            m = (choice == r) & requesting
            want = jnp.where(m, remaining, 0.0)
            cum = jnp.cumsum(want)
            cum_before = jnp.concatenate(
                [jnp.zeros(1, dtype=jnp.float64), cum[:-1]])
            take = jnp.minimum(want,
                               jnp.maximum(avail[r] - cum_before, 0.0))
            # infeasible (source, r) pairs are static: spilled service
            viol = viol + take * (~feas[:, r]).astype(np.float64)
            remaining = remaining - take
            avail = avail.at[r].set(jnp.maximum(avail[r] - cum[-1], 0.0))
    routed = cap - avail
    drop_route = remaining

    # ---- autoscaling: CarbonScaler greedy over the (R, K) table ----
    need = jnp.ceil(routed / cap1)
    lo = jnp.maximum(float(spec.min_rep), rep0 - spec.max_step)
    hi = jnp.minimum(float(spec.kmax), rep0 + spec.max_step)
    desired = jnp.minimum(jnp.maximum(need, lo), hi)
    span = spec.peak_w - spec.base_w
    if spec.budget is None:
        n = desired
    else:
        K = spec.kmax
        k_idx = np.arange(1, K + 1, dtype=np.float64)[None, :]
        reg_of = np.repeat(np.arange(R), K)
        w = jnp.clip(routed[:, None] - (k_idx - 1.0) * cap1, 0.0, cap1)
        g = ((spec.base_w + span * (w / cap1))
             * spec.dt / 3600.0 * c_row[:, None] / 1000.0)
        mand = k_idx <= lo[:, None]
        opt = (k_idx > lo[:, None]) & (k_idx <= desired[:, None])
        mand_g = jnp.cumsum(jnp.where(mand, g, 0.0).ravel())[-1]
        # zero-gram guard: free entries admitted first, no overflow div
        freeg = g <= 0.0
        eff = w / jnp.where(freeg, 1.0, g)
        score2 = jnp.where(opt, jnp.where(freeg, -jnp.inf, -eff),
                           jnp.inf).ravel()
        order = jnp.argsort(score2)                    # stable by default
        gs = jnp.where(opt, g, 0.0).ravel()[order]
        cum_g = jnp.cumsum(gs)
        admit = opt.ravel()[order] & (mand_g + cum_g <= spec.budget)
        reg_sorted = jnp.asarray(reg_of)[order]
        counts = jnp.sum(admit[:, None]
                         & (reg_sorted[:, None] == np.arange(R)[None, :]),
                         axis=0)
        n = lo + counts
    served = jnp.minimum(routed, n * cap1)
    drop_cap = routed - served
    pw = n * spec.base_w + span * (served / cap1)
    emis = pw * spec.dt / 3600.0 * c_row / 1000.0
    mod = spec.gain * served / cap
    return n, (mod, routed, served, drop_route, drop_cap, viol, emis)


def simulate_traffic_jax(requests, region_intensity, cfg: TrafficConfig,
                         interval_s: float = 300.0) -> TrafficResult:
    """Standalone scan of `traffic_step` over all T epochs (float64)."""
    requests = np.asarray(requests, dtype=np.float64)
    region_intensity = np.asarray(region_intensity, dtype=np.float64)
    spec = TrafficSpec.from_config(cfg, interval_s)
    R = spec.R
    if requests.shape != region_intensity.shape or requests.ndim != 2 \
            or requests.shape[1] != R:
        raise ValueError(f"requests {requests.shape} / intensity "
                         f"{region_intensity.shape} must be (T, {R})")

    def step(rep, x):
        req_row, c_row = x
        rep1, outs = traffic_step(spec, rep, req_row, c_row)
        return rep1, outs + (rep1,)

    with jax.enable_x64(True):
        rep0 = jnp.full(R, float(spec.min_rep), dtype=jnp.float64)
        _, ys = jax.jit(lambda xs: lax.scan(step, rep0, xs))(
            (jnp.asarray(requests), jnp.asarray(region_intensity)))
        _, routed, served, drop_route, drop_cap, viol, emis, reps = (
            np.asarray(y) for y in ys)
    return TrafficResult(
        requests=requests, routed=routed,
        replicas=np.rint(reps).astype(np.int64),
        served=served, dropped_route=drop_route, dropped_cap=drop_cap,
        violations=viol, emissions_g=emis,
        max_capacity=spec.max_capacity, interval_s=float(interval_s))
