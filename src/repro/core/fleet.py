"""Vectorized fleet simulator: N Carbon Containers advanced in lockstep.

The scalar `repro.core.simulator.simulate` runs one pure-Python loop per
container, which makes population sweeps (paper Figs 11-16) and
CarbonScaler/Ecovisor-style fleet studies prohibitively slow. This module
advances a whole fleet per monitoring interval using NumPy array state.

Array-state layout
------------------
`FleetState` holds one `(N,)` array per scalar `ContainerState` field:

    slice_idx      int64   current slice (index into the FamilyTables)
    duty           f64     duty-cycle quota set by the last decision
    suspended      bool    container released / idle-parked
    migrating_s    f64     remaining stop-and-copy downtime (0 = none)
    migrate_target int64   destination slice while migrating (-1 = none)
    dwell          int64   intervals since the last migration
    emissions_g, energy_wh, work_done, throttled_integral,
    demand_integral, suspended_s, elapsed_s           f64 accumulators
    migrations     int64
    time_on_slice_s  (N, S+1) f64; column S counts suspended time
    recent_peak    f64     rolling W-interval demand peak (precomputed as a
                           (T, N) sliding-window-max matrix before the loop)

Decision-kernel masking scheme
------------------------------
Each policy exposes `decide_batch(tables, state, demand, c, target, eps)`
returning `(kind, duty, target_slice)` arrays; branchy scalar `decide`
logic becomes boolean masks applied in scalar-return order (a `decided`
mask freezes containers that already matched an earlier return site, so
mask priority == scalar control flow). The step function then partitions
the fleet into {migrating, suspend, resume, migrate, stay} masks, computes
power/served per partition with the precomputed per-slice (base_w, peak_w,
multiple) lookup tables, and applies one fused accounting update.

Every arithmetic expression mirrors the scalar path term-for-term, so an
N=1 fleet reproduces `simulate()` bit-for-bit (see tests/test_fleet.py).
Per-container heterogeneity is first-class: `targets`, `epsilon`,
`state_gb` broadcast per container, `demand` is `(T, N)`, and `carbon`
accepts a `(T, N)` matrix for mixed-region (stacked-trace) fleets.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from repro.cluster.migration import MigrationCostModel
from repro.cluster.slices import SliceFamily
from repro.core.policy import (K_MIGRATE, K_RESUME, K_STAY, K_SUSPEND,
                               _budget_batch)
from repro.core.simulator import SimConfig, SimResult

_PEAK_WINDOW = 6          # ContainerState.observe_demand default (n=6)


@dataclass
class FleetState:
    """(N,)-array mirror of `ContainerState` (see module docstring)."""
    slice_idx: np.ndarray
    duty: np.ndarray
    suspended: np.ndarray
    migrating_s: np.ndarray
    migrate_target: np.ndarray
    dwell: np.ndarray
    emissions_g: np.ndarray
    energy_wh: np.ndarray
    work_done: np.ndarray
    throttled_integral: np.ndarray
    demand_integral: np.ndarray
    suspended_s: np.ndarray
    elapsed_s: np.ndarray
    migrations: np.ndarray
    time_on_slice_s: np.ndarray
    recent_peak: np.ndarray              # rolling-window demand peak

    @classmethod
    def init(cls, n: int, n_slices: int, baseline_idx: int) -> "FleetState":
        z = lambda: np.zeros(n, dtype=np.float64)
        return cls(
            slice_idx=np.full(n, baseline_idx, dtype=np.int64),
            duty=np.ones(n, dtype=np.float64),
            suspended=np.zeros(n, dtype=bool),
            migrating_s=z(),
            migrate_target=np.full(n, -1, dtype=np.int64),
            dwell=np.full(n, 10 ** 6, dtype=np.int64),   # as simulate() seeds
            emissions_g=z(), energy_wh=z(), work_done=z(),
            throttled_integral=z(), demand_integral=z(),
            suspended_s=z(), elapsed_s=z(),
            migrations=np.zeros(n, dtype=np.int64),
            time_on_slice_s=np.zeros((n, n_slices + 1), dtype=np.float64),
            recent_peak=z(),
        )


@dataclass
class FleetResult:
    """Per-container result arrays; `result(i)` extracts a scalar SimResult."""
    emissions_g: np.ndarray
    energy_wh: np.ndarray
    work_done: np.ndarray
    work_demanded: np.ndarray
    throttled_integral: np.ndarray
    migrations: np.ndarray
    suspended_s: np.ndarray
    elapsed_s: np.ndarray
    time_on_slice_s: np.ndarray          # (N, S+1); last column = suspended
    slice_names: tuple                   # S names + ("suspended",)
    baseline_cap: float
    power_series: Optional[np.ndarray] = None   # (T, N) when record=True
    served_series: Optional[np.ndarray] = None  # (T, N) when record=True
    unmetered_g: Optional[np.ndarray] = None    # (N,) emissions billed during
    #                                             power-telemetry gaps

    @property
    def n(self) -> int:
        return self.emissions_g.shape[0]

    @property
    def hours(self) -> np.ndarray:
        return self.elapsed_s / 3600.0

    @property
    def avg_carbon_rate(self) -> np.ndarray:
        return self.emissions_g / np.maximum(self.hours, 1e-12)

    @property
    def avg_throttle_pct(self) -> np.ndarray:
        return (100.0 * self.throttled_integral
                / np.maximum(self.elapsed_s, 1e-9) / self.baseline_cap)

    @property
    def suspended_frac(self) -> np.ndarray:
        return self.suspended_s / np.maximum(self.elapsed_s, 1e-9)

    def time_on_slice(self, i: int) -> dict:
        el = max(float(self.elapsed_s[i]), 1e-9)
        return {name: float(s) / el
                for name, s in zip(self.slice_names, self.time_on_slice_s[i])
                if s > 0.0}

    def result(self, i: int) -> SimResult:
        hours = float(self.elapsed_s[i]) / 3600.0
        el = max(float(self.elapsed_s[i]), 1e-9)
        return SimResult(
            avg_carbon_rate=float(self.emissions_g[i]) / max(hours, 1e-12),
            avg_throttle_pct=100.0 * float(self.throttled_integral[i]) / el
            / self.baseline_cap,
            work_done=float(self.work_done[i]),
            work_demanded=float(self.work_demanded[i]),
            energy_kwh=float(self.energy_wh[i]) / 1000.0,
            migrations=int(self.migrations[i]),
            suspended_frac=float(self.suspended_s[i]) / el,
            time_on_slice=self.time_on_slice(i),
            emissions_g=float(self.emissions_g[i]),
            hours=hours,
            series=None,
        )

    def results(self) -> list:
        return [self.result(i) for i in range(self.n)]


def _carbon_matrix(carbon, T: int, interval_s: float):
    """(T,) or (T, N) intensity values at each interval start."""
    if isinstance(carbon, np.ndarray):
        return carbon
    t = np.arange(T, dtype=np.float64) * interval_s
    if hasattr(carbon, "intensity_series"):
        return carbon.intensity_series(t)
    return np.array([carbon.intensity(float(x)) for x in t])


def _prepare_run_inputs(demand, carbon, targets, epsilon, state_gb,
                        demand_scale, interval_s: float):
    """Shared `run()` prologue for the fleet and jax backends: demand
    shaping/validation, carbon-matrix resolution, per-container
    broadcasts. One implementation so the two backends can never drift
    on what inputs they accept."""
    demand = np.asarray(demand, dtype=np.float64)
    if demand.ndim == 1:
        demand = demand[:, None]
    T, N = demand.shape
    if demand_scale is not None and np.any(np.asarray(demand_scale) != 1.0):
        demand = demand * demand_scale
    if demand.size and demand.min() < 0.0:
        raise ValueError("fleet demand must be non-negative")
    cmat = _carbon_matrix(carbon, T, interval_s)
    if cmat.ndim not in (1, 2) or cmat.shape[0] != T or (
            cmat.ndim == 2 and cmat.shape[1] != N):
        raise ValueError(f"carbon matrix shape {cmat.shape} does not "
                         f"match demand (T={T}, N={N}); expected (T,) "
                         f"or (T, N)")
    targets = np.broadcast_to(np.asarray(targets, dtype=np.float64), (N,))
    epsilon = np.broadcast_to(np.asarray(epsilon, dtype=np.float64), (N,))
    state_gb = np.broadcast_to(np.asarray(state_gb, dtype=np.float64), (N,))
    return demand, cmat, targets, epsilon, state_gb, T, N


class _LoopScratch:
    """Preallocated per-epoch temporaries for `FleetSimulator._loop`.

    The stepping loop previously allocated ~20 fresh (N,) arrays every
    epoch (masks, gathers, plant-step temps); reusing fixed buffers via
    ufunc `out=` keeps the arithmetic and its term order identical (the
    1e-9 scalar-parity suite pins this) while removing the allocator
    from the hot loop. Measured effect (see the fleet_sweep benchmark
    notes in benchmarks/figs.py): ~6-8% wall-clock at N~5000, neutral at
    N~500 — NumPy's small-block cache already amortizes most allocation,
    so only the single-pass ufunc-out rewrites pay; `np.take(..., out=)`
    needs mode="clip" to match fancy indexing's fast path, and rewrites
    that would split one `np.where` into two passes are kept as-is.
    """

    __slots__ = ("migm", "i1", "m1", "m2", "m3", "m4", "m5", "m6",
                 "m7", "m8", "m9", "f1", "f2", "f3", "f4", "f5", "f6",
                 "f7", "f8", "f9", "f10", "f11", "f12")

    def __init__(self, n: int):
        for name in ("migm", "m1", "m2", "m3", "m4", "m5", "m6", "m7",
                     "m8", "m9"):
            setattr(self, name, np.empty(n, dtype=bool))
        self.i1 = np.empty(n, dtype=np.int64)
        for name in ("f1", "f2", "f3", "f4", "f5", "f6", "f7", "f8", "f9",
                     "f10", "f11", "f12"):
            setattr(self, name, np.empty(n, dtype=np.float64))


class FleetSimulator:
    """Advance N containers under one policy with array state.

    Usage::

        sim = FleetSimulator(paper_family())
        res = sim.run(policy, demand,          # (T, N) utilization matrix
                      carbon,                  # provider | (T,) | (T, N)
                      targets=45.0)            # scalar or (N,)
    """

    def __init__(self, family: SliceFamily, interval_s: float = 300.0,
                 suspend_releases_slice: bool = True,
                 migration: Optional[MigrationCostModel] = None):
        self.family = family
        self.tables = family.tables()
        self.interval_s = float(interval_s)
        self.suspend_releases_slice = suspend_releases_slice
        self.mig = migration or MigrationCostModel()

    # -- inputs -----------------------------------------------------------

    def _carbon_matrix(self, carbon, T: int):
        """(T,) or (T, N) intensity values at each interval start."""
        return _carbon_matrix(carbon, T, self.interval_s)

    # -- main loop --------------------------------------------------------

    def run(self, policy, demand, carbon, targets, epsilon=0.05,
            state_gb=1.0, demand_scale=1.0, record: bool = False,
            carbon_obs=None, power_gap=None) -> FleetResult:
        """`carbon_obs` (optional (T,) or (T, N) matrix) splits the
        signal plane from the billing plane: decision kernels (and
        their precomputed power budgets) consume the *observed*
        intensity while emissions stay billed at the true `carbon` —
        see `repro.robustness`. `power_gap` (optional (T,) 0/1 vector)
        marks power-telemetry outage epochs; emissions during gaps are
        still billed but also accumulated into
        `FleetResult.unmetered_g` (the meter saw nothing)."""
        t = self.tables
        dt = self.interval_s
        (demand, cmat, targets, epsilon, state_gb, T, N) = \
            _prepare_run_inputs(demand, carbon, targets, epsilon, state_gb,
                                demand_scale, self.interval_s)
        if carbon_obs is not None:
            carbon_obs = np.asarray(carbon_obs, dtype=np.float64)
            if carbon_obs.shape not in ((T,), (T, N)):
                raise ValueError(f"carbon_obs shape {carbon_obs.shape} "
                                 f"does not match (T={T},) or (T, N={N})")
        gap = None
        if power_gap is not None:
            gap = np.asarray(power_gap, dtype=np.float64)
            if gap.shape != (T,):
                raise ValueError(f"power_gap shape {gap.shape} != (T={T},)")
        cf = _closed_form_kind(policy)
        if cf is not None:
            return self._run_closed_form(cf, demand, cmat, targets, epsilon,
                                         record, cmat_obs=carbon_obs,
                                         gap=gap)
        n_slices = len(t.multiple)
        st = FleetState.init(N, n_slices, t.baseline_idx)
        rows = np.arange(N)
        power_series = np.zeros((T, N)) if record else None
        served_series = np.zeros((T, N)) if record else None
        power = np.zeros(N)
        served = np.zeros(N)
        scratch = _LoopScratch(N)
        unmet = np.zeros(N) if gap is not None else None

        # loop-invariant precomputations (hoisted out of the time loop):
        # rolling-window demand peaks (ContainerState.recent_peak) ...
        peak_mat = demand.copy()
        for k in range(1, _PEAK_WINDOW):
            np.maximum(peak_mat[k:], demand[:-k], out=peak_mat[k:])
        # ... per-interval power budgets for the decision kernels (from
        # the observed feed — the controller has no other signal) ...
        cmat2 = cmat if cmat.ndim == 2 else cmat[:, None]
        if carbon_obs is not None:
            cmat2 = (carbon_obs if carbon_obs.ndim == 2
                     else carbon_obs[:, None])
        budget_mat = _budget_batch(targets[None, :], cmat2, epsilon[None, :])
        # ... and the demand-integral increments
        ddt_mat = demand * dt

        with np.errstate(divide="ignore", invalid="ignore"):
            self._loop(policy, st, demand, cmat, targets, epsilon, state_gb,
                       budget_mat, peak_mat, ddt_mat, power_series,
                       served_series, power, served, rows, T, N, n_slices,
                       scratch, cmat_obs=carbon_obs, gap=gap, unmet=unmet)
        # elapsed accumulates dt once per interval for every container;
        # hoisted out of the loop as the identical sequential sum
        st.elapsed_s.fill(float(np.cumsum(np.full(T, dt))[-1]) if T else 0.0)

        return FleetResult(
            emissions_g=st.emissions_g,
            energy_wh=st.energy_wh,
            work_done=st.work_done,
            work_demanded=st.demand_integral,
            throttled_integral=st.throttled_integral,
            migrations=st.migrations,
            suspended_s=st.suspended_s,
            elapsed_s=st.elapsed_s,
            time_on_slice_s=st.time_on_slice_s,
            slice_names=t.names + ("suspended",),
            baseline_cap=float(t.multiple[t.baseline_idx]),
            power_series=power_series,
            served_series=served_series,
            unmetered_g=unmet,
        )

    def _loop(self, policy, st, demand, cmat, targets, epsilon, state_gb,
              budget_mat, peak_mat, ddt_mat, power_series, served_series,
              power, served, rows, T, N, n_slices, scratch,
              cmat_obs=None, gap=None, unmet=None):
        t = self.tables
        dt = self.interval_s
        record = power_series is not None
        c_is_mat = cmat.ndim == 2
        obs_is_mat = cmat_obs is not None and cmat_obs.ndim == 2
        sc = scratch
        for n in range(T):
            d = demand[n]
            c = cmat[n] if c_is_mat else float(cmat[n])
            if cmat_obs is None:
                c_dec = c
            else:
                c_dec = cmat_obs[n] if obs_is_mat else float(cmat_obs[n])
            st.demand_integral += ddt_mat[n]
            st.recent_peak = peak_mat[n]

            power.fill(0.0)
            served.fill(0.0)

            # ---- migration in progress: both slices powered, no work ----
            migm = np.greater(st.migrating_s, 0.0, out=sc.migm)
            any_mig = np.count_nonzero(migm)
            if any_mig:
                dstc = np.where(migm, st.migrate_target, 0)
                np.take(t.base_w, st.slice_idx, out=sc.f1, mode="clip")
                np.take(t.base_w, dstc, out=sc.f2, mode="clip")
                np.add(sc.f1, sc.f2, out=sc.f1)
                np.copyto(power, sc.f1, where=migm)

            kind, dy, tg = policy.decide_batch(t, st, d, c_dec, targets,
                                               epsilon, budget=budget_mat[n])
            # fold the migrating containers out of `kind` so the per-action
            # masks below need no separate `& act` (copy, not in-place:
            # decide_batch's return stays the policy's to reuse)
            if any_mig:
                kind = np.where(migm, -1, kind)
            counts = np.bincount(np.maximum(kind, 0, out=sc.i1),
                                 minlength=4)

            # ---- suspend ------------------------------------------------
            if counts[K_SUSPEND]:
                m_sus = np.equal(kind, K_SUSPEND, out=sc.m1)
                st.suspended[m_sus] = True
                st.suspended_s[m_sus] += dt
                if not self.suspend_releases_slice:
                    power[m_sus] = t.base_w[st.slice_idx[m_sus]]

            # ---- resume (joins the run path below) ----------------------
            m_res = None
            if counts[K_RESUME]:
                m_res = np.equal(kind, K_RESUME, out=sc.m2)
                st.suspended[m_res] = False
                has_t = np.greater_equal(tg, 0, out=sc.m3)
                np.logical_and(m_res, has_t, out=has_t)
                st.slice_idx[has_t] = tg[has_t]
                np.copyto(st.duty, dy, where=m_res)

            m_stay = np.equal(kind, K_STAY, out=sc.m4)
            np.copyto(st.duty, dy, where=m_stay)

            # ---- migrate ------------------------------------------------
            subm = None
            if counts[K_MIGRATE]:
                m_mig = np.equal(kind, K_MIGRATE, out=sc.m5)
                st.migrations[m_mig] += 1
                dstc = np.where(m_mig, tg, 0)
                bw = np.maximum(np.take(t.bw_gbps, st.slice_idx, out=sc.f1, mode="clip"),
                                np.take(t.bw_gbps, dstc, out=sc.f2, mode="clip"),
                                out=sc.f1)
                mig_s = self.mig.stop_and_copy_time_batch(state_gb, bw)
                down = np.divide(np.minimum(mig_s, dt, out=sc.f2), dt,
                                 out=sc.f2)
                p_mig = np.add(np.take(t.base_w, st.slice_idx, out=sc.f3, mode="clip"),
                               np.take(t.base_w, dstc, out=sc.f4, mode="clip"),
                               out=sc.f3)
                np.copyto(st.duty, dy, where=m_mig)
                longm = np.greater_equal(mig_s, dt, out=sc.m6)
                np.logical_and(m_mig, longm, out=longm)
                # long migration: whole interval down, src slice accounted
                np.copyto(st.migrate_target, tg, where=longm)
                np.copyto(st.migrating_s, np.subtract(mig_s, dt, out=sc.f4),
                          where=longm)
                np.copyto(power, p_mig, where=longm)
                # sub-interval: rest of the interval served on the dest
                subm = np.logical_and(m_mig, np.logical_not(longm, out=sc.m7),
                                      out=sc.m7)
                if not np.count_nonzero(subm):
                    subm = None
                else:
                    np.copyto(st.slice_idx, tg, where=subm)
                    st.dwell[subm] = 0

            # ---- plant step for running containers ----------------------
            if m_res is None:
                full = m_stay
            else:
                full = np.logical_or(m_res, m_stay, out=sc.m8)
            if subm is not None or np.count_nonzero(full):
                mult_cur = np.take(t.multiple, st.slice_idx, out=sc.f5, mode="clip")
                base_cur = np.take(t.base_w, st.slice_idx, out=sc.f6, mode="clip")
                cap = np.multiply(
                    mult_cur,
                    np.minimum(np.maximum(st.duty, 0.0, out=sc.f7), 1.0,
                               out=sc.f7),
                    out=sc.f7)
                srv = np.minimum(d, cap, out=sc.f8)
                util = np.divide(srv, mult_cur, out=sc.f9)
                #    in [0, 1]: demand >= 0, duty clipped -> the scalar
                #    path's util clamp is an identity
                pw = np.take(t.peak_w, st.slice_idx, out=sc.f10, mode="clip")
                np.subtract(pw, base_cur, out=pw)
                np.multiply(pw, util, out=pw)
                np.add(base_cur, pw, out=pw)
                np.copyto(power, pw, where=full)
                np.copyto(served, srv, where=full)
                if subm is not None:
                    # down * p_mig + (1 - down) * pw, built in scratch
                    np.subtract(1.0, down, out=sc.f11)
                    np.multiply(sc.f11, pw, out=sc.f11)
                    np.multiply(down, p_mig, out=sc.f12)
                    np.add(sc.f12, sc.f11, out=sc.f12)
                    np.copyto(power, sc.f12, where=subm)
                    np.subtract(1.0, down, out=sc.f11)
                    np.multiply(sc.f11, srv, out=sc.f11)
                    np.copyto(served, sc.f11, where=subm)

            # ---- fused accounting (scalar _account, vectorized) ---------
            st.energy_wh += np.divide(np.multiply(power, dt, out=sc.f1),
                                      3600.0, out=sc.f1)
            np.multiply(power, c, out=sc.f2)
            np.divide(sc.f2, 1000.0, out=sc.f2)
            np.multiply(sc.f2, dt, out=sc.f2)
            np.divide(sc.f2, 3600.0, out=sc.f2)
            st.emissions_g += sc.f2
            if unmet is not None and gap[n] > 0.0:
                # telemetry outage: emissions happen but the meter is
                # blind — bill them AND tally the unmetered share
                unmet += sc.f2
            st.work_done += np.multiply(served, dt, out=sc.f3)
            np.subtract(d, served, out=sc.f4)
            np.maximum(0.0, sc.f4, out=sc.f4)
            st.throttled_integral += np.multiply(sc.f4, dt, out=sc.f4)
            tos_col = np.where(st.suspended, n_slices, st.slice_idx)
            st.time_on_slice_s[rows, tos_col] += dt
            if record:
                power_series[n] = power
                served_series[n] = served

            # ---- migration progress + dwell (after accounting) ----------
            if any_mig:
                st.migrating_s[migm] -= dt
                done = np.less_equal(st.migrating_s, 0.0, out=sc.m9)
                np.logical_and(migm, done, out=done)
                st.slice_idx[done] = st.migrate_target[done]
                st.migrate_target[done] = -1
                st.dwell[done] = 0
            if counts[K_MIGRATE]:
                st.dwell[(kind >= 0) & (kind != K_MIGRATE)] += 1
            elif any_mig:
                st.dwell[kind >= 0] += 1
            else:
                st.dwell += 1

    # -- closed-form fast path for state-free policies --------------------

    def _run_closed_form(self, cf: str, demand, cmat, targets, epsilon,
                         record: bool, cmat_obs=None, gap=None
                         ) -> FleetResult:
        """Whole-(T, N)-matrix evaluation for policies whose per-interval
        outcome does not depend on simulation state.

        CarbonAgnosticPolicy never leaves the baseline slice; for
        SuspendResumePolicy the suspension state each interval equals its
        (state-independent) over-target predicate — evaluated on the
        *observed* intensity when `cmat_obs` is given, while emissions
        stay billed at the true `cmat`. Accumulators use np.cumsum
        (sequential adds) so results stay bit-identical to the stepping
        loop.
        """
        t = self.tables
        dt = self.interval_s
        T, N = demand.shape
        b = t.baseline_idx
        mult_b = t.multiple[b]
        base_b = t.base_w[b]
        span_b = t.peak_w[b] - base_b
        c2 = cmat if cmat.ndim == 2 else cmat[:, None]
        if cmat_obs is None:
            c2_obs = c2
        else:
            c2_obs = cmat_obs if cmat_obs.ndim == 2 else cmat_obs[:, None]

        srv = np.minimum(demand, mult_b)     # duty 1.0 on the baseline slice
        util = srv / mult_b
        pw = base_b + span_b * util          # util in [0, 1] (demand >= 0)
        n_slices = len(t.multiple)
        tos = np.zeros((N, n_slices + 1), dtype=np.float64)
        suspended_s = np.zeros(N, dtype=np.float64)
        migrations = np.zeros(N, dtype=np.int64)
        elapsed = float(np.cumsum(np.full(T, dt))[-1]) if T else 0.0
        elapsed_s = np.full(N, elapsed)

        parts = []                           # step matrices to accumulate
        if cf == "suspend_resume":
            # over <=> rate(power(u)) > (1-eps)*target, u == util bitwise
            # (predicate on the observed feed; billing stays on c2)
            over = pw * c2_obs / 1000.0 > (1.0 - epsilon) * targets
            p_sus = 0.0 if self.suspend_releases_slice else base_b
            power = np.where(over, p_sus, pw)
            served = np.where(over, 0.0, srv)
            # accumulate dt (not elapsed - suspended) for bit-parity with
            # the scalar loop's per-interval accumulation at any dt
            parts.append(np.where(over, dt, 0.0))
            parts.append(np.where(over, 0.0, dt))
        else:                                # carbon-agnostic
            power = pw
            served = srv
            tos[:, b] = elapsed_s

        def _chain(a, *ops):         # in-place op chain: same term order,
            for f, v in ops:         # fewer (T, N) temporaries
                f(a, v, out=a)
            return a

        parts = [_chain(power * c2, (np.divide, 1000.0), (np.multiply, dt),
                        (np.divide, 3600.0)),
                 _chain(power * dt, (np.divide, 3600.0)),
                 served * dt,
                 demand * dt,
                 _chain(np.maximum(0.0, demand - served),
                        (np.multiply, dt))] + parts
        if gap is not None:
            # unmetered emissions: the per-epoch emission part masked to
            # the telemetry-gap epochs, accumulated in the same walk
            parts.append(parts[0] * gap[:, None])
        # sequential per-row accumulation (== the stepping loop's add order,
        # hence bit-identical); one fused (T, k*N) walk
        stacked = np.concatenate(parts, axis=1)
        acc = np.zeros(stacked.shape[1], dtype=np.float64)
        for row in stacked:
            acc += row
        emis, energy, work, dem, thr = (acc[k * N:(k + 1) * N]
                                        for k in range(5))
        k_next = 5
        if cf == "suspend_resume":
            suspended_s = acc[5 * N:6 * N]
            tos[:, n_slices] = suspended_s
            tos[:, b] = acc[6 * N:7 * N]
            k_next = 7
        unmetered = (acc[k_next * N:(k_next + 1) * N] if gap is not None
                     else None)

        return FleetResult(
            emissions_g=emis,
            energy_wh=energy,
            work_done=work,
            work_demanded=dem,
            throttled_integral=thr,
            migrations=migrations,
            suspended_s=suspended_s,
            elapsed_s=elapsed_s,
            time_on_slice_s=tos,
            slice_names=t.names + ("suspended",),
            baseline_cap=float(t.multiple[t.baseline_idx]),
            power_series=power if record else None,
            served_series=served if record else None,
            unmetered_g=unmetered,
        )


def _closed_form_kind(policy) -> Optional[str]:
    """Exact-type dispatch: subclasses may override decide(), so only the
    stock baseline policies take the closed-form path."""
    from repro.core.policy import (CarbonAgnosticPolicy,
                                   SuspendResumePolicy)
    if type(policy) is CarbonAgnosticPolicy:
        return "agnostic"
    if type(policy) is SuspendResumePolicy:
        return "suspend_resume"
    return None


# ---------------------------------------------------------------------------
# Multi-policy batching: dispatch decide_batch over contiguous column blocks
# ---------------------------------------------------------------------------

class _StateView:
    """Sliced view of a FleetState for one policy's column block."""

    __slots__ = ("slice_idx", "suspended", "dwell", "recent_peak")

    def __init__(self, st: FleetState, sl: slice):
        self.slice_idx = st.slice_idx[sl]
        self.suspended = st.suspended[sl]
        self.dwell = st.dwell[sl]
        self.recent_peak = st.recent_peak[sl]


class BlockPolicy:
    """Compose several policies into one fleet, each owning a contiguous
    column block. Lets a whole (policy x target x trace) sweep advance in a
    single FleetSimulator.run, amortizing per-step overhead across all
    policies (containers never interact, so results are unchanged)."""

    def __init__(self, blocks):
        self.blocks = list(blocks)        # [(policy, slice), ...]

    def decide_batch(self, t, state, demand, c, target, eps, budget=None):
        n = demand.shape[0]
        kind = np.empty(n, dtype=np.int64)
        duty = np.empty(n, dtype=np.float64)
        tgt = np.empty(n, dtype=np.int64)
        for pol, sl in self.blocks:
            c_b = c[sl] if isinstance(c, np.ndarray) else c
            b_b = budget[sl] if budget is not None else None
            k, dy, tg = pol.decide_batch(t, _StateView(state, sl),
                                         demand[sl], c_b, target[sl], eps[sl],
                                         budget=b_b)
            kind[sl] = k
            duty[sl] = dy
            tgt[sl] = tg
        return kind, duty, tgt


# ---------------------------------------------------------------------------
# Population sweep on the fleet path (backend="fleet" in sweep_population)
# ---------------------------------------------------------------------------

class _FaultContext:
    """Materialized signal-plane faults for one sweep (host-side, shared
    verbatim by the fleet and jax backends so degraded signals are
    identical by construction): the degraded `ObservedSignal`, the
    observed and true (T, R) region matrices (or (T, n_tr) dense
    matrices on placement-free sweeps), and the (T,) power-telemetry
    gap vector (None when the plan has no gaps)."""

    __slots__ = ("signal", "obs_reg", "true_reg", "gap_vec", "faults")

    def __init__(self, signal, obs_reg, true_reg, gap_vec, faults):
        self.signal = signal
        self.obs_reg = obs_reg
        self.true_reg = true_reg
        self.gap_vec = gap_vec
        self.faults = faults


def _prepare_sweep_inputs(traces, carbon, targets, cfg_base, demand_scale,
                          placement, plan_fn, tile: bool = True,
                          energy=None, faults=None):
    """Shared sweep prologue for the fleet and jax backends (one
    implementation so the two can never drift on what sweeps they
    accept): stack the equal-length traces into the policy-block demand
    matrix, tile targets, and — with a placement engine — compute the
    shared region plan on the real n_tr-column fleet via `plan_fn` and
    substitute the planned per-container carbon matrix. Returns
    (demand_one, tgt_one, carbon, plan, n_tr, n_tg, grid_up, fault_ctx).

    With ``faults`` (a `repro.robustness.FaultPlan`), the *planner*
    (and via `plan.region_intensity` every downstream controller layer
    — traffic routing, elastic budgets/forecasts) consumes the degraded
    observed feed, while the returned billing `carbon` is gathered from
    the TRUE region matrix; `plan_fn` receives the fault plan so the
    planner threads the seeded migration-failure mask. ``fault_ctx``
    carries the observed/true split for the caller.

    With ``tile=False`` (the jax backend's memory-lean placed sweep)
    the demand matrix stays compact — (T, n_tr), NOT target-tiled —
    and the planned carbon matrix is not materialized (``carbon`` comes
    back as None; the caller feeds the plan's indexed form to the
    simulator instead). At the N=1M target (n_tr=100k x n_tg=10,
    T=288) the tiled (T, N) f64 matrices are ~2.3 GB apiece on the
    host; the compact path never builds them.

    With ``energy`` (a `repro.energy.EnergyConfig`; requires
    `placement`), the grid-event layer perturbs the engine's (T, R)
    region-intensity matrix *before planning* — shocks multiply the
    grid intensity the planner (and the traffic/elasticity layers,
    via `plan.region_intensity`) consume — and the (T, R) `grid_up`
    outage mask is returned for the supply simulation."""
    if isinstance(traces, np.ndarray) and traces.ndim == 2:
        stack = np.asarray(traces, dtype=np.float64)   # (T, n_tr) direct
    else:
        traces = [np.asarray(tr, dtype=np.float64) for tr in traces]
        lengths = {len(tr) for tr in traces}
        if len(lengths) != 1:
            raise ValueError("fleet backend needs equal-length traces; "
                             f"got lengths {sorted(lengths)}")
        stack = np.stack(traces, axis=1)               # (T, n_tr)
    n_tr = stack.shape[1]
    n_tg = len(targets)
    demand_one = np.tile(stack, (1, n_tg)) if tile else stack
    tgt_one = np.repeat(np.asarray(targets, dtype=np.float64), n_tr)

    plan = None
    grid_up = None
    fault_ctx = None
    T = stack.shape[0]
    if energy is not None and placement is None:
        raise ValueError("energy=EnergyConfig(...) requires a placement "
                         "engine (placement=...): the supply side — "
                         "solar, battery, grid events — is per region")
    if placement is not None:
        if float(placement.interval_s) != float(cfg_base.interval_s):
            raise ValueError(
                f"placement engine plans on interval_s="
                f"{placement.interval_s} but the sweep simulates at "
                f"interval_s={cfg_base.interval_s}; construct the engine "
                f"with the sweep's interval")
        import copy
        if energy is not None:
            from repro.energy.supply import event_matrices
            raw = placement._region_matrix(T)
            shock_mult, grid_up = event_matrices(energy.events, T,
                                                 placement.n_regions)
            placement = copy.copy(placement)
            placement.regions = raw * shock_mult
        if faults is not None:
            from repro import obs
            from repro.robustness.degrade import observe_intensity
            from repro.robustness.faults import power_gap_vector
            with obs.span("sweep.faults"):
                # TRUE regional signal (post grid shocks — those are
                # physical); the controller plane sees the degraded feed
                true_reg = placement._region_matrix(T)
                signal = observe_intensity(true_reg, faults,
                                           cfg_base.interval_s)
                placement = copy.copy(placement)
                placement.regions = signal.observed
                fault_ctx = _FaultContext(signal, signal.observed, true_reg,
                                          power_gap_vector(faults, T),
                                          faults)
        demand_plan = stack
        if demand_scale is not None and np.any(
                np.asarray(demand_scale) != 1.0):
            demand_plan = stack * demand_scale
        plan = plan_fn(placement, demand_plan, faults)
        if tile:
            if fault_ctx is None:
                carbon = np.tile(plan.carbon_matrix(), (1, n_tg))
            else:
                # bill at the TRUE intensity of each planned region;
                # the plan's own matrix is the observed feed
                dense_true = fault_ctx.true_reg[np.arange(T)[:, None],
                                                plan.assign[:T]]
                carbon = np.tile(dense_true, (1, n_tg))
        else:
            carbon = None
    elif faults is not None:
        from repro.robustness.degrade import observe_intensity
        from repro.robustness.faults import power_gap_vector
        if carbon is None:
            raise ValueError("faults without a placement engine need an "
                             "explicit carbon signal to degrade")
        true_mat = _carbon_matrix(carbon, T, cfg_base.interval_s)
        true2 = true_mat if true_mat.ndim == 2 else true_mat[:, None]
        signal = observe_intensity(true2, faults, cfg_base.interval_s)
        obs = (signal.observed if true_mat.ndim == 2
               else signal.observed[:, 0])
        fault_ctx = _FaultContext(signal, obs, true_mat,
                                  power_gap_vector(faults, T), faults)
        carbon = true_mat
    return (demand_one, tgt_one, carbon, plan, n_tr, n_tg, grid_up,
            fault_ctx)


def _prepare_traffic(traffic, plan, T: int, interval_s: float):
    """Shared traffic prologue for the fleet and jax sweep backends:
    generate the population's (T, R) request tensor and run the NumPy
    traffic pipeline against the plan's region-intensity table. Returns
    (ArrivalTensor, TrafficResult). Requires a placement plan — the
    traffic layers are per *region*, so without a region assignment
    there is nothing to route between."""
    from repro.traffic.arrivals import request_matrix
    from repro.traffic.sim import simulate_traffic
    if plan is None:
        raise ValueError("traffic=TrafficConfig(...) requires a placement "
                         "engine (placement=...): routing and autoscaling "
                         "are per region")
    R = plan.n_regions
    if traffic.population.n_regions != R:
        raise ValueError(f"traffic population spans "
                         f"{traffic.population.n_regions} regions but the "
                         f"placement engine has {R}")
    arr = request_matrix(traffic.population, T, interval_s)
    res = simulate_traffic(arr.requests, plan.region_intensity[:T], traffic,
                           interval_s)
    return arr, res


def _prepare_energy(energy, family, plan, comp, T: int, interval_s: float,
                    grid_up, region_mat=None):
    """Shared energy prologue for the fleet and jax sweep backends: run
    the host supply simulation on the compact fleet's per-region
    flexible load and gather the two per-container signals. Returns
    ``(spec, SupplyResult, solar (T, R), cap_cols (T, n_tr),
    ceff_cols (T, n_tr))``.

    `comp` is the compact (T, n_tr) demand *after* demand_scale and the
    traffic modulation (pinned layer order: demand_scale -> traffic ->
    energy -> elasticity). The region load is the fleet's flexible
    power, linear in demand (see repro.energy.supply docstring), so
    enforcing the virtual cap by scaling demand with `cap_frac` lands
    exactly on the supplied power. Both backends call this one helper —
    the supply ledger and the `energy_*` row metrics are bit-identical
    across backends; only the *application* of cap_frac/c_eff differs
    (host gather on the fleet path, in-scan fold on the jax path).

    `region_mat` overrides the (T, R) grid intensity the *physical*
    supply runs on — under signal-plane faults the plan's matrix is the
    degraded observed feed, but electrons mix at the TRUE intensity."""
    from repro.energy.supply import (EnergySpec, flex_w_per_unit,
                                     simulate_supply, solar_series)
    R = plan.n_regions
    n_tr = comp.shape[1]
    spec = EnergySpec.from_config(energy, n_tr, R, interval_s,
                                  flex_w_per_unit(family))
    solar = solar_series(energy.solar, T, R, interval_s, spec.solar_peak_w)
    assign = plan.assign[:T]
    load = np.zeros((T, R), dtype=np.float64)
    for r in range(R):
        # where= keeps the per-region reduction temp at one bool mask
        # (matters at the N=100k scale gate)
        np.sum(comp, axis=1, where=(assign == r), out=load[:, r])
    load *= spec.load_coef
    grid_c = (plan.region_intensity[:T] if region_mat is None
              else region_mat[:T])
    sres = simulate_supply(load, solar, grid_c, grid_up, spec)
    rows = np.arange(T)[:, None]
    cap_cols = sres.cap_frac[rows, assign]
    ceff_cols = sres.c_eff[rows, assign]
    return spec, sres, solar, cap_cols, ceff_cols


def sweep_population_fleet(policies: dict, family: SliceFamily, traces,
                           carbon, targets: Sequence[float],
                           cfg_base: SimConfig,
                           demand_scale: float = 1.0,
                           placement=None, traffic=None,
                           elasticity=None, energy=None,
                           faults=None) -> list:
    """Fleet-backed `sweep_population`: batches every (policy x target x
    trace) combination into ONE FleetSimulator.run call (policy-major
    column blocks via BlockPolicy) and emits the same aggregate rows, in
    the same order, as the scalar backend.

    With `placement` (a `repro.cluster.placement.PlacementEngine`), each
    trace column is first assigned a region per epoch by the placement
    layer — the plan is computed once on the real n_tr-column fleet (so
    engine capacity applies to the actual containers, not a
    target-duplicated copy) and shared by every (policy, target) block,
    so all combinations are compared under the same region schedule —
    and the planned per-container carbon matrix replaces `carbon`. Rows
    then also carry `placement_migrations_mean` and
    `placement_overhead_g_mean`.

    With `traffic` (a `repro.traffic.TrafficConfig`; requires
    `placement`), a request population is routed and autoscaled over
    the plan's regions first, and each container's demand is modulated
    by its region's serving load (`TrafficResult.demand_mod`). Rows
    then also carry the `traffic_*` serving metrics.

    With `elasticity` (a `repro.core.elasticity.ElasticityConfig`;
    requires `placement`), the per-container CarbonScaler level
    allocation runs over the scaled + traffic-modulated compact demand
    before the fleet simulation; the fleet then advances on each
    container's *served* demand (unserved work deferred through the
    backlog) and rows carry the `elastic_*` metrics.

    With `energy` (a `repro.energy.EnergyConfig`; requires
    `placement`), the per-region virtual energy supply — solar +
    battery + event-perturbed grid — runs over the compact fleet's
    flexible load: grid-intensity shocks perturb the matrix the
    planner/traffic/elasticity layers consume, each container's demand
    is clamped by its region's virtual power-cap fraction, the fleet
    (and the elasticity layer) bill emissions at the delivered mix's
    *effective* intensity, and rows carry the `energy_*` supply
    metrics. Order is pinned — demand_scale, then traffic, then
    energy, then elasticity — and shared with the jax backend so the
    parity chain holds with all layers on.

    With `faults` (a `repro.robustness.FaultPlan`), every controller
    layer — decision kernels, placement planner, traffic routing,
    elastic budgets and forecasts — consumes the degraded *observed*
    carbon feed while emissions stay billed at the true one; planned
    migrations fail per the seeded mask (stop-and-copy paid, container
    stays put, capped-backoff retry) and power-telemetry gaps accrue
    `unmetered_g`. Rows gain the `fault_*` summaries.
    """
    (demand_one, tgt_one, carbon, plan, n_tr, n_tg, grid_up, fault_ctx) = \
        _prepare_sweep_inputs(traces, carbon, targets, cfg_base,
                              demand_scale, placement,
                              lambda eng, d, flt: eng.plan(
                                  d, state_gb=cfg_base.state_gb, faults=flt),
                              energy=energy, faults=faults)
    per_pol = n_tr * n_tg
    T = demand_one.shape[0]
    gap_vec = fault_ctx.gap_vec if fault_ctx is not None else None
    carbon_obs = None
    if fault_ctx is not None:
        if plan is not None:
            # plan.carbon_matrix() gathers plan.region_intensity — which
            # IS the observed feed under faults
            carbon_obs = np.tile(plan.carbon_matrix(), (1, n_tg))
        else:
            obs = fault_ctx.obs_reg
            carbon_obs = np.tile(obs, (1, n_tg)) if obs.ndim == 2 else obs

    traffic_summary = None
    mod_cols = None
    if traffic is not None:
        _, tres = _prepare_traffic(traffic, plan, T, cfg_base.interval_s)
        mod = tres.demand_mod(traffic.demand_gain)       # (T, R)
        mod_cols = mod[np.arange(T)[:, None], plan.assign[:T]]   # (T, n_tr)
        traffic_summary = tres.summary()
        if elasticity is None and energy is None:
            demand_one = demand_one * np.tile(mod_cols, (1, n_tg))

    # compact pipeline for the energy/elasticity layers: scale + traffic
    # modulation applied once at (T, n_tr) width, layers in pinned order
    comp = None
    if energy is not None or elasticity is not None:
        comp = demand_one[:, :n_tr]
        if demand_scale is not None and np.any(
                np.asarray(demand_scale) != 1.0):
            comp = comp * demand_scale
        if mod_cols is not None:
            comp = comp * mod_cols

    energy_summary = None
    ceff_reg = None
    if energy is not None:
        _, sres, _, cap_cols, ceff_cols = _prepare_energy(
            energy, family, plan, comp, T, cfg_base.interval_s, grid_up,
            region_mat=(fault_ctx.true_reg if fault_ctx is not None
                        else None))
        energy_summary = sres.summary()
        comp = comp * cap_cols              # enforce the virtual cap
        carbon = np.tile(ceff_cols, (1, n_tg))   # bill the delivered mix
        ceff_reg = sres.c_eff               # forecast the delivered mix too
        if fault_ctx is not None:
            # the controller observes the delivered mix through the same
            # degraded feed: scale the true effective intensity by the
            # per-region observed/true grid ratio
            tr = fault_ctx.true_reg[:T]
            safe = np.where(tr > 0.0, tr, 1.0)
            ratio = np.where(tr > 0.0,
                             fault_ctx.obs_reg[:T] / safe, 1.0)
            ceff_obs_reg = sres.c_eff * ratio
            rows_t = np.arange(T)[:, None]
            carbon_obs = np.tile(ceff_obs_reg[rows_t, plan.assign[:T]],
                                 (1, n_tg))
            ceff_reg = ceff_obs_reg         # controller-side forecast feed

    elastic_summary = None
    if elasticity is not None:
        if plan is None:
            raise ValueError("elasticity requires placement")
        from repro.core.elasticity import simulate_elastic
        eres = simulate_elastic(
            comp, carbon[:, :n_tr], elasticity, cfg_base.interval_s,
            carbon_forecast=_elastic_carbon_forecast(
                plan, T, elasticity, cfg_base.interval_s,
                region_mat=ceff_reg),
            budget_series=_elastic_budget_series(
                plan, T, elasticity, cfg_base.interval_s))
        demand_one = np.tile(eres.demand_served(), (1, n_tg))
        demand_scale = 1.0          # already applied ahead of the layer
        elastic_summary = eres.summary()
    elif energy is not None:
        demand_one = np.tile(comp, (1, n_tg))
        demand_scale = 1.0          # already applied ahead of the layer

    sim = FleetSimulator(family, interval_s=cfg_base.interval_s,
                         suspend_releases_slice=cfg_base.suspend_releases_slice)
    run_kw = dict(epsilon=cfg_base.epsilon, state_gb=cfg_base.state_gb,
                  demand_scale=demand_scale, carbon_obs=carbon_obs,
                  power_gap=gap_vec)

    # state-free policies go straight through the closed-form path; the
    # stateful rest share one stepping run via BlockPolicy column blocks
    results = {}                          # name -> (FleetResult, col offset)
    loop_pols = []
    for name, mk_policy in policies.items():
        pol = mk_policy()
        if _closed_form_kind(pol) is not None:
            results[name] = (sim.run(pol, demand_one, carbon, tgt_one,
                                     **run_kw), 0)
        else:
            loop_pols.append((name, pol))
    if len(loop_pols) == 1:                   # skip block-dispatch overhead
        name, pol = loop_pols[0]
        results[name] = (sim.run(pol, demand_one, carbon, tgt_one,
                                 **run_kw), 0)
    elif loop_pols:
        blocks = [(pol, slice(p * per_pol, (p + 1) * per_pol))
                  for p, (_, pol) in enumerate(loop_pols)]
        demand = np.tile(demand_one, (1, len(loop_pols)))
        tgt_vec = np.tile(tgt_one, len(loop_pols))
        carbon_blk = carbon
        if isinstance(carbon, np.ndarray) and carbon.ndim == 2:
            carbon_blk = np.tile(carbon, (1, len(loop_pols)))
        blk_kw = dict(run_kw)
        if isinstance(carbon_obs, np.ndarray) and carbon_obs.ndim == 2:
            blk_kw["carbon_obs"] = np.tile(carbon_obs, (1, len(loop_pols)))
        res = sim.run(BlockPolicy(blocks), demand, carbon_blk, tgt_vec,
                      **blk_kw)
        for p, (name, _) in enumerate(loop_pols):
            results[name] = (res, p * per_pol)

    fault_summary = None
    if fault_ctx is not None:
        fault_summary = fault_ctx.signal.summary()
        if plan is not None and plan.failed_migrations is not None:
            fault_summary["fault_failed_migrations_mean"] = float(
                np.mean(plan.failed_migrations))
    return _aggregate_sweep_rows(policies, results, targets, n_tr, plan,
                                 traffic_summary, elastic_summary,
                                 energy_summary, fault_summary)


def _elastic_carbon_forecast(plan, T: int, elasticity, interval_s: float,
                             region_mat=None) -> np.ndarray:
    """(T, n_tr) carbon estimates for the elasticity layer: forecast on
    the plan's compact (T, R) region matrix, then gather per container.
    The jax backend forecasts the same region matrix and applies its
    R-way select in-scan, so the two see bit-identical estimates
    (forecast-then-gather, never gather-then-forecast — containers
    migrate between regions mid-trace). `region_mat` overrides the
    forecast signal: with the energy layer on, the scaler plans against
    the delivered mix's (T, R) effective intensity — the series it is
    actually billed at — not the raw grid."""
    from repro.carbon.forecast import forecast_series
    cmode = {"oracle": "oracle", "persistence": "persistence",
             "forecast": "diurnal_ar1"}[elasticity.forecast]
    period = max(1, int(round(24 * 3600.0 / float(interval_s))))
    reg = plan.region_intensity if region_mat is None else region_mat
    chat_reg = forecast_series(reg, cmode,
                               period_steps=period, rho=elasticity.rho)
    return chat_reg[np.arange(T)[:, None], plan.assign[:T]]


def _elastic_budget_series(plan, T: int, elasticity, interval_s: float):
    """Shared shaped-budget series for the sweep backends (or None).

    The shaping signal is the placed fleet's mean carbon intensity,
    gathered from the plan exactly as written here; the jax sweep calls
    this same helper so both backends hand `shaped_budget_series` the
    same (T,) floats and allocate identical level counts."""
    if not elasticity.shape_budget or elasticity.budget_g_per_epoch is None:
        return None
    from repro.core.elasticity import shaped_budget_series
    dense = plan.region_intensity[np.arange(T)[:, None], plan.assign[:T]]
    return shaped_budget_series(dense.mean(axis=1), elasticity, interval_s)


def _aggregate_sweep_rows(policies: dict, results: dict, targets, n_tr: int,
                          plan=None, traffic_summary=None,
                          elastic_summary=None, energy_summary=None,
                          fault_summary=None) -> list:
    """Fold per-container FleetResult arrays into the sweep's aggregate
    rows. `results` maps policy name -> (FleetResult, column offset);
    shared by the fleet and jax sweep backends so the two emit the same
    rows in the same order. Aggregation is sliced-array arithmetic, not
    per-container Python loops — at fleet scale (N >= 5000) the loop
    version costs tens of milliseconds, which is real money against the
    jax backend's steady-state sweep times."""
    # hoist the whole-fleet derived arrays out of the per-target loop
    # (the avg_* properties rebuild (N,) arrays on every access)
    derived = {}
    for name, (res, off) in results.items():
        if id(res) not in derived:
            el = np.maximum(res.elapsed_s, 1e-9)[:, None]
            tos = res.time_on_slice_s
            derived[id(res)] = (res.avg_carbon_rate, res.avg_throttle_pct,
                                res.suspended_frac,
                                np.where(tos > 0.0, tos / el, 0.0))
    rows = []
    for ti, target in enumerate(targets):
        for name in policies:
            res, off = results[name]
            rates_a, thr_a, susp_a, tos_fr = derived[id(res)]
            sl = slice(off + ti * n_tr, off + (ti + 1) * n_tr)
            rates = rates_a[sl]
            thr = thr_a[sl]
            # time_on_slice, aggregated: mean over containers of the
            # per-container fraction, counting only containers that
            # spent time there (res.time_on_slice(i)'s `if s > 0` rule)
            fracs = tos_fr[sl].sum(axis=0) / n_tr
            slice_time = {k: float(v)
                          for k, v in zip(res.slice_names, fracs)
                          if v != 0.0}
            row = {
                "policy": name, "target": target,
                "carbon_rate_mean": float(np.mean(rates)),
                "carbon_rate_std": float(np.std(rates)),
                "throttle_mean": float(np.mean(thr)),
                "throttle_std": float(np.std(thr)),
                "migrations_mean": float(np.mean(res.migrations[sl])),
                "suspended_frac_mean": float(np.mean(susp_a[sl])),
                "time_on_slice": slice_time,
            }
            if plan is not None:
                # one shared n_tr-column plan: identical per target
                row["placement_migrations_mean"] = float(
                    np.mean(plan.migrations))
                row["placement_overhead_g_mean"] = float(
                    np.mean(plan.overhead_g))
            if traffic_summary is not None:
                # the traffic layer runs once on the shared plan, ahead
                # of the policy/target fan-out: identical per row
                row.update(traffic_summary)
            if elastic_summary is not None:
                # same sharing as traffic: one elastic pass per sweep
                row.update(elastic_summary)
            if energy_summary is not None:
                # one supply simulation per sweep, shared by backends
                row.update(energy_summary)
            if fault_summary is not None:
                # degraded-signal + failed-migration summaries; one
                # observation pass per sweep, shared by backends
                row.update(fault_summary)
                if res.unmetered_g is not None:
                    row["fault_unmetered_g_mean"] = float(
                        np.mean(res.unmetered_g[sl]))
            rows.append(row)
    return rows
