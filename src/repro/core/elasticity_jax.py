"""JAX backend for the per-container elasticity layer.

One jitted `lax.scan` over epochs runs the (N, K) CarbonScaler greedy
of `repro.core.elasticity` at fleet scale: float64 (scoped
`enable_x64`), per-epoch temporaries only (N,)/(N, K) — nothing
(T, N) is materialized on device beyond the input/output streams.

Carbon comes either dense (T, N) or as the placed fleet's
`(region_mat (T, R), codes (T, N) int32)` pair; the indexed form
derives each epoch's per-container intensity with the same R-way
select chain as `repro.core.fleet_jax._fleet_scan`, which reproduces
the host gather bit-exactly. Both forecasts are precomputed host-side
by the same `repro.carbon.forecast` functions the NumPy backend uses —
carbon on the tiny (T, R) region matrix when indexed, demand on the
(T, N) matrix (one extra demand-sized xs stream; the scan itself
carries nothing (T, N)) — so estimates, greedy scores, and allocated
level counts are bit-identical to the NumPy backend by construction.

The scan runs separately from the fleet scan on purpose: the fleet
scan executes once per device shard, and duplicating the (N·K,)
argsort per shard would multiply the dominant cost by the shard
count. Instead this scan runs once at compact width and its served
demand feeds the (unchanged) sharded fleet scan.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.carbon.forecast import forecast_series
from repro.core.elasticity import (ElasticityConfig, ElasticResult,
                                   shaped_budget_series)

_SCAN_CACHE: dict = {}


def _cumsum(x):
    """Prefix sum as an explicit associative scan: `jnp.cumsum` lowers
    to a whole-length reduce_window, which the TPU compiler takes
    minutes to build for (N·K,) f64 at fleet scale; the scan's log-depth
    tree compiles in seconds. Either association differs from NumPy's
    sequential cumsum only in the last bits."""
    return lax.associative_scan(jnp.add, x)


def _spec_key(cfg: ElasticityConfig, interval_s: float):
    return (cfg.k_levels, cfg.unit_capacity, cfg.base_w, cfg.peak_w,
            cfg.min_level, cfg.max_step, cfg.budget_g_per_epoch,
            cfg.forecast, cfg.rho, float(interval_s))


def _build_scan(cfg: ElasticityConfig, interval_s: float, n: int,
                R, record: bool):
    """Jitted epoch scan for one (config, width, carbon-layout)."""
    dt = float(interval_s)
    capw = cfg.capw(dt)
    span = cfg.peak_w - cfg.base_w
    K = cfg.k_levels
    budget = cfg.budget_g_per_epoch
    indexed = R is not None
    k_idx = np.arange(1, K + 1, dtype=np.float64)[None, :]
    con_of = np.repeat(np.arange(n), K)

    def emis_g(lev, work_w, chat):
        pw = lev * cfg.base_w + span * (work_w / capw)
        return jnp.sum(pw * dt / 3600.0 * chat / 1000.0)

    def step(st, x):
        prev, backlog, scal = st
        if indexed:
            d, dhat, bud, code, c_row, chat_row = x
            # R-way select chain (bit-exact vs host gather, same idiom
            # as _fleet_scan)
            c = jnp.full(code.shape, c_row[0], dtype=jnp.float64)
            chat = jnp.full(code.shape, chat_row[0], dtype=jnp.float64)
            for r in range(1, R):
                c = jnp.where(code == r, c_row[r], c)
                chat = jnp.where(code == r, chat_row[r], chat)
        else:
            d, dhat, bud, c, chat = x

        want = dhat * dt + backlog
        need = jnp.ceil(want / capw)
        lo = jnp.maximum(float(cfg.min_level), prev - cfg.max_step)
        hi = jnp.minimum(float(cfg.k_levels), prev + cfg.max_step)
        desired = jnp.minimum(jnp.maximum(need, lo), hi)
        if budget is None:
            alloc = desired
        else:
            w = jnp.clip(want[:, None] - (k_idx - 1.0) * capw, 0.0, capw)
            g = ((cfg.base_w + span * (w / capw))
                 * dt / 3600.0 * chat[:, None] / 1000.0)
            mand = k_idx <= lo[:, None]
            opt = (k_idx > lo[:, None]) & (k_idx <= desired[:, None])
            mand_g = _cumsum(jnp.where(mand, g, 0.0).ravel())[-1]
            # zero-gram guard: free levels first, no overflow division
            freeg = g <= 0.0
            eff = w / jnp.where(freeg, 1.0, g)
            score = jnp.where(opt, jnp.where(freeg, -jnp.inf, -eff),
                              jnp.inf).ravel()
            order = jnp.argsort(score)                 # stable by default
            gs = jnp.where(opt, g, 0.0).ravel()[order]
            cum = _cumsum(gs)
            admit = opt.ravel()[order] & (mand_g + cum <= bud)
            counts = jnp.zeros(n, dtype=jnp.float64).at[
                jnp.asarray(con_of)[order]].add(admit.astype(jnp.float64))
            alloc = lo + counts

        offered = d * dt
        est_w = jnp.minimum(want, alloc * capw)
        srv = jnp.minimum(offered + backlog, alloc * capw)
        backlog = backlog + offered - srv
        est_step = emis_g(alloc, est_w, chat)
        act_step = emis_g(alloc, srv, c)
        if budget is None:
            viol = jnp.zeros((), dtype=jnp.float64)
        else:
            mand_w = jnp.minimum(want, lo * capw)
            mand_total = emis_g(lo, mand_w, chat)
            viol = (est_step
                    > jnp.maximum(bud, mand_total) + 1e-9).astype(
                        jnp.float64)
        # scalar accumulators: est_g, act_g, viol, level_epochs
        scal = scal + jnp.stack([est_step, act_step, viol,
                                 jnp.sum(alloc)])
        ys = (srv / dt, alloc.astype(jnp.int32)) if record else srv / dt
        return (alloc, backlog, scal), ys

    def _elastic_scan(xs):
        st0 = (jnp.full(n, float(cfg.min_level), dtype=jnp.float64),
               jnp.zeros(n, dtype=jnp.float64),
               jnp.zeros(4, dtype=jnp.float64))
        return lax.scan(step, st0, xs)

    return jax.jit(_elastic_scan)


def _budget_array(budget_series, cfg: ElasticityConfig, dt: float,
                  T: int, signal_fn):
    """(T,) per-epoch budgets for the scan (zeros when uncapped).

    The scan's no-budget branch is static, so the placeholder zeros are
    never read. Shaped budgets are computed host-side — same helper,
    same floats as the NumPy backend.
    """
    if budget_series is not None:
        bud = np.asarray(budget_series, dtype=np.float64)
        if bud.shape != (T,):
            raise ValueError(f"budget_series must be ({T},); "
                             f"got {bud.shape}")
        return bud
    if cfg.budget_g_per_epoch is None:
        return np.zeros(T, dtype=np.float64)
    if cfg.shape_budget:
        return shaped_budget_series(signal_fn(), cfg, dt)
    return np.full(T, float(cfg.budget_g_per_epoch))


def simulate_elastic_jax(demand, carbon, cfg: ElasticityConfig,
                         interval_s: float = 300.0,
                         record: bool = False,
                         budget_series=None,
                         carbon_forecast=None) -> ElasticResult:
    """JAX port of `repro.core.elasticity.simulate_elastic`.

    demand : (T, N) demand rate (host array)
    carbon : dense (T, N), or `(region_mat (T, R), codes (T, N))` for
             the placed-fleet indexed layout
    With `record=False` the per-epoch levels are not streamed out
    (`ElasticResult.levels` is empty) — the summary totals still
    include them via an in-scan accumulator.
    `budget_series` overrides the per-epoch budgets (see
    `simulate_elastic`); when omitted and `cfg.shape_budget` is set it
    is derived host-side from the mean-over-containers carbon signal,
    matching the NumPy backend bit for bit.
    `carbon_forecast` overrides the matrix the carbon forecaster runs
    on — the scaler then plans against that signal while billing
    `carbon` (the observed/true split under signal-plane faults):
    (T, R) region form in indexed mode, (T, N) dense otherwise. The
    fleet backend forecasts the very same matrix host-side, so the two
    stay bit-identical (forecast-then-gather on both).
    """
    demand = np.asarray(demand, dtype=np.float64)
    if demand.ndim != 2:
        raise ValueError(f"demand must be (T, N); got {demand.shape}")
    T, n = demand.shape
    dt = float(interval_s)
    period = max(1, int(round(24 * 3600.0 / dt)))
    fmode = {"oracle": "oracle", "persistence": "persistence",
             "forecast": "diurnal_ar1"}[cfg.forecast]
    dhat = forecast_series(demand, fmode, period_steps=period, rho=cfg.rho)

    indexed = isinstance(carbon, tuple)
    if indexed:
        region_mat, codes = carbon
        region_mat = np.asarray(region_mat, dtype=np.float64)
        codes = np.asarray(codes, dtype=np.int32)
        if region_mat.ndim != 2 or region_mat.shape[0] != T \
                or codes.shape != (T, n):
            raise ValueError(f"indexed carbon shapes {region_mat.shape} / "
                             f"{codes.shape} do not match demand (T={T}, "
                             f"N={n})")
        R = region_mat.shape[1]
        fc_src = region_mat
        if carbon_forecast is not None:
            fc_src = np.asarray(carbon_forecast, dtype=np.float64)
            if fc_src.shape != region_mat.shape:
                raise ValueError(f"carbon_forecast shape {fc_src.shape} "
                                 f"must match the region matrix "
                                 f"{region_mat.shape}")
        chat_reg = forecast_series(fc_src, fmode, period_steps=period,
                                   rho=cfg.rho)
        bud = _budget_array(budget_series, cfg, dt, T, lambda:
                            region_mat[np.arange(T)[:, None],
                                       codes].mean(axis=1))
        xs = (demand, dhat, bud, codes, region_mat, chat_reg)
    else:
        carbon = np.asarray(carbon, dtype=np.float64)
        if carbon.shape != demand.shape:
            raise ValueError(f"carbon {carbon.shape} must match demand "
                             f"{demand.shape}")
        R = None
        fc_src = carbon
        if carbon_forecast is not None:
            fc_src = np.asarray(carbon_forecast, dtype=np.float64)
            if fc_src.shape != carbon.shape:
                raise ValueError(f"carbon_forecast shape {fc_src.shape} "
                                 f"must match carbon {carbon.shape}")
        chat = forecast_series(fc_src, fmode, period_steps=period,
                               rho=cfg.rho)
        bud = _budget_array(budget_series, cfg, dt, T,
                            lambda: carbon.mean(axis=1))
        xs = (demand, dhat, bud, carbon, chat)

    key = (_spec_key(cfg, dt), T, n, R, bool(record))
    fn = _SCAN_CACHE.get(key)
    with jax.enable_x64(True):
        if fn is None:
            fn = _build_scan(cfg, dt, n, R, record)
            _SCAN_CACHE[key] = fn
        dev = jax.devices()[0]
        xs_dev = tuple(jax.device_put(a, dev) for a in xs)
        (prev, backlog, scal), ys = fn(xs_dev)
        served_rate = np.asarray((ys[0] if record else ys))
        levels = (np.asarray(ys[1], dtype=np.int64) if record
                  else np.zeros((0, n), dtype=np.int64))
        backlog = np.asarray(backlog)
        scal = np.asarray(scal)

    return ElasticResult(levels=levels, served_w=served_rate * dt,
                         offered_w=demand * dt, backlog=backlog,
                         est_emissions_g=float(scal[0]),
                         emissions_g=float(scal[1]),
                         cap_violations=int(round(float(scal[2]))),
                         interval_s=dt,
                         level_epochs=int(round(float(scal[3]))))
