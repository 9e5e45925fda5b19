"""Multi-region placement layer on top of the vectorized fleet substrate.

The paper's Carbon Containers enforce a per-container g·CO2e/hr cap with
vertical scaling, suspend/resume and *migration* (§3.2); CarbonScaler and
CASPER extend the idea across regions, moving work toward cleaner grids.
This module adds that cross-region dimension above `FleetSimulator`
(`repro.core.fleet`): each monitoring epoch a `PlacementEngine` assigns
every container in an (N,) fleet to one of R regions (stacked carbon
traces), deciding migrate/stay by weighing the projected carbon saving
over an amortization horizon against the `MigrationCostModel`
stop-and-copy cost, with hysteresis and per-region capacity limits.

Decision model (identical in the scalar reference and the batch kernel)
----------------------------------------------------------------------
At epoch n, container i currently in region a with demand d:

    p_est   = base_b + (peak_b - base_b) * min(d / mult_b, 1)   [W]
    save(r) = p_est * (c[a] - c[r]) / 1000 * H_hr               [g, horizon]
    cost(r) = 2*base_b * mig_s / 3600 * 0.5*(c[a]+c[r]) / 1000  [g, one move]
    net(r)  = save(r) - (1 + hysteresis) * cost(r)

`p_est` is a persistence forecast on the baseline slice (the placement
layer is policy-agnostic: it cannot see which slice the enforcement
policy will pick, so it prices the move at baseline power — conservative
on both sides of the ledger). `mig_s` is the Fig.-7 stop-and-copy time at
the cross-region link bandwidth; during it both endpoints idle
(`2*base_b`) at the mean of the two grids' intensities. A container
requests the argmax-net region when `net > 0` and its dwell since the
last placement move is at least `min_dwell` (hysteresis + dwell kill
oscillation on flat or noisy traces).

Capacity uses two-phase admission in preference rounds: occupancy is
snapshotted at epoch start; round k admits each still-unplaced
requester's k-th surviving choice in container-index order while
`capacity[r] - occupancy[r]` slots remain (a denied choice is struck and
the container falls through toward its next-cleanest positive-net
region, mirroring the policy layer's fall-through idiom); slots freed by
departures become available next epoch. This keeps the greedy scalar
reference and the cumsum-masked batch kernel bit-identical
(`tests/test_placement.py` pins parity to 1e-9) and guarantees no region
ever exceeds capacity.

The planned assignment gathers per-container carbon traces
(`PlacementPlan.carbon_matrix`) that feed straight into
`FleetSimulator.run`, so the enforcement policies simulate unchanged on
the region each container actually occupies; placement stop-and-copy
overhead is accounted separately (`PlacementPlan.overhead_g`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from repro.cluster.migration import MigrationCostModel
from repro.cluster.slices import SliceFamily


@dataclass(frozen=True)
class PlacementConfig:
    """Knobs of the migrate/stay decision (see module docstring)."""
    horizon_intervals: int = 12      # amortize one move over H epochs
    hysteresis: float = 0.10         # saving must beat (1+h) * cost
    min_dwell: int = 6               # epochs pinned after a placement move
    link_gbps: float = 0.25          # cross-region (WAN) state bandwidth
    capacity: Optional[object] = None  # per-region container cap: int | (R,)

    def capacity_vector(self, n_regions: int) -> Optional[np.ndarray]:
        if self.capacity is None:
            return None
        raw = np.broadcast_to(np.asarray(self.capacity), (n_regions,))
        cap = raw.astype(np.int64)
        if (cap != np.asarray(raw, dtype=np.float64)).any():
            raise ValueError(f"per-region capacity must be integral, got "
                             f"{raw!r}")
        if (cap < 1).any():
            raise ValueError("per-region capacity must be >= 1")
        return cap.copy()


@dataclass
class PlacementPlan:
    """Epoch-by-epoch region assignment for an (N,) fleet.

    `assign[n, i]` is container i's region during epoch n (post-decision:
    a move decided at epoch n serves epoch n from the destination, with
    the stop-and-copy downtime priced into `overhead_g`/`downtime_s`).

    The JAX planner leaves its (T, N) int32 assignments on the device, in
    `assign_device`, where the fleet scan reads them, and passes
    ``assign=None``: `assign` is then made from the device copy on first
    read (pulled, widened to int64) and kept, a writable host array like
    the NumPy planner's. `demand_device` pairs the host demand that
    planner was given with the device copy it pushed.
    """
    assign: Optional[np.ndarray]     # (T, N) int64 region index
    migrations: np.ndarray           # (N,) placement moves per container
    overhead_g: np.ndarray           # (N,) stop-and-copy emissions (g)
    downtime_s: np.ndarray           # (N,) stop-and-copy downtime (s)
    region_intensity: np.ndarray     # (T, R) g/kWh per region per epoch
    region_names: tuple
    initial: np.ndarray              # (N,) pre-epoch-0 region index
    failed_migrations: Optional[np.ndarray] = None   # (N,) failed attempts
    # (T,) capacity-admission preference rounds per epoch; kept by the JAX
    # planner only, and None where no admission ran (uncapped, trivial)
    admission_rounds: Optional[np.ndarray] = None
    assign_device: Optional[object] = None   # (T, N) int32 jax.Array
    demand_device: Optional[tuple] = None    # (host (T, N), device copy)

    @property
    def n_regions(self) -> int:
        return self.region_intensity.shape[1]

    def carbon_matrix(self) -> np.ndarray:
        """(T, N) per-container intensity under the planned assignment."""
        T = self.assign.shape[0]
        return self.region_intensity[np.arange(T)[:, None], self.assign]

    def occupancy(self) -> np.ndarray:
        """(T, R) containers per region per epoch."""
        T, _ = self.assign.shape
        R = self.n_regions
        out = np.zeros((T, R), dtype=np.int64)
        for r in range(R):
            out[:, r] = (self.assign == r).sum(axis=1)
        return out


def _read_assign(plan) -> Optional[np.ndarray]:
    if plan._assign is None and plan.assign_device is not None:
        plan._assign = np.asarray(plan.assign_device).astype(np.int64)
    return plan._assign


def _write_assign(plan, value) -> None:
    plan._assign = value


# `assign` stays a dataclass field (the constructor sets it through the
# setter); the property makes the JAX planner's host copy on first read
PlacementPlan.assign = property(_read_assign, _write_assign)


@dataclass
class PlacementResult:
    """A placed fleet run: the inner FleetResult plus the plan that drove
    it. Total emissions add the placement stop-and-copy overhead."""
    plan: PlacementPlan
    fleet: object                    # repro.core.fleet.FleetResult
    static_fleet: object = None      # optional no-migration baseline

    @property
    def total_emissions_g(self) -> np.ndarray:
        return self.fleet.emissions_g + self.plan.overhead_g

    @property
    def carbon_efficiency(self) -> np.ndarray:
        """Work done per kg CO2e, overhead included (paper's merit figure)."""
        kg = np.maximum(self.total_emissions_g / 1000.0, 1e-12)
        return self.fleet.work_done / kg

    @property
    def saving_vs_static_pct(self) -> float:
        """Fleet-total emissions saving vs the no-migration baseline."""
        if self.static_fleet is None:
            raise ValueError("run with compare_static=True to populate "
                             "the static baseline")
        stat = float(self.static_fleet.emissions_g.sum())
        moved = float(self.total_emissions_g.sum())
        return 100.0 * (stat - moved) / max(stat, 1e-12)


class PlacementEngine:
    """Assign an (N,) fleet across R regions, one decision per epoch.

    Usage::

        eng = PlacementEngine(paper_family(), providers, config=cfg)
        plan = eng.plan(demand)                       # (T, N) assignment
        res = eng.run(policy, demand, targets=45.0)   # placed fleet run

    `regions` is either a (T, R) intensity matrix or a sequence of
    providers exposing `intensity_series` (see repro.carbon.intensity).
    """

    def __init__(self, family: SliceFamily, regions,
                 interval_s: float = 300.0,
                 migration: Optional[MigrationCostModel] = None,
                 config: Optional[PlacementConfig] = None,
                 region_names: Optional[Sequence[str]] = None):
        self.family = family
        self.tables = family.tables()
        self.regions = regions
        self.interval_s = float(interval_s)
        self.mig = migration or MigrationCostModel()
        self.config = config or PlacementConfig()
        if isinstance(regions, np.ndarray):
            n_regions = regions.shape[1]
        else:
            n_regions = len(regions)
        if n_regions < 1:
            raise ValueError("need at least one region")
        if region_names is None:
            region_names = tuple(f"r{i}" for i in range(n_regions))
        if len(region_names) != n_regions:
            raise ValueError("region_names length does not match regions")
        self.region_names = tuple(region_names)
        self.n_regions = n_regions

    # -- inputs -----------------------------------------------------------

    def _region_matrix(self, T: int) -> np.ndarray:
        """(T, R) intensity at each epoch start."""
        if isinstance(self.regions, np.ndarray):
            m = np.asarray(self.regions, dtype=np.float64)
            if m.ndim != 2 or m.shape[1] != self.n_regions:
                raise ValueError(f"region matrix shape {m.shape}; expected "
                                 f"(T, {self.n_regions})")
            if m.shape[0] < T:
                raise ValueError(f"region matrix covers {m.shape[0]} epochs; "
                                 f"demand needs {T}")
            return m[:T]
        t = np.arange(T, dtype=np.float64) * self.interval_s
        return np.stack([p.intensity_series(t) for p in self.regions],
                        axis=1)

    def _initial_assignment(self, N: int, initial,
                            cap: Optional[np.ndarray]) -> np.ndarray:
        R = self.n_regions
        if cap is not None and int(cap.sum()) < N:
            raise ValueError(f"total capacity {int(cap.sum())} < fleet "
                             f"size {N}")
        if initial is None:
            if cap is None:
                assign = np.arange(N, dtype=np.int64) % R  # round-robin
            else:
                # capacity-aware round-robin: cycle regions, skipping
                # full ones, so uneven capacity vectors stay feasible
                rep_r = np.repeat(np.arange(R, dtype=np.int64), cap)
                rep_k = np.concatenate([np.arange(c) for c in cap])
                assign = rep_r[np.lexsort((rep_r, rep_k))][:N]
        else:
            assign = np.asarray(initial, dtype=np.int64).copy()
            if assign.shape != (N,):
                raise ValueError(f"initial assignment shape {assign.shape}; "
                                 f"expected ({N},)")
            if assign.size and (assign.min() < 0 or assign.max() >= R):
                raise ValueError("initial assignment region out of range")
        if cap is not None:
            occ = np.bincount(assign, minlength=R)
            if (occ > cap).any():
                raise ValueError("initial assignment exceeds region capacity")
        return assign

    def _prep(self, demand, state_gb, initial):
        demand = np.asarray(demand, dtype=np.float64)
        if demand.ndim == 1:
            demand = demand[:, None]
        if demand.ndim != 2:
            raise ValueError("demand must be (T,) or (T, N)")
        if demand.size and demand.min() < 0.0:
            raise ValueError("placement demand must be non-negative")
        T, N = demand.shape
        cmat = self._region_matrix(T)
        cap = self.config.capacity_vector(self.n_regions)
        assign = self._initial_assignment(N, initial, cap)
        state_gb = np.broadcast_to(
            np.asarray(state_gb, dtype=np.float64), (N,))
        # per-container stop-and-copy time & idle-power gram coefficient,
        # hoisted: state size and link bandwidth are epoch-invariant
        mig_s = self.mig.stop_and_copy_time_batch(
            state_gb, np.broadcast_to(self.config.link_gbps, (N,)))
        base_b = float(self.tables.base_w[self.tables.baseline_idx])
        cost0 = 2.0 * base_b * mig_s / 3600.0
        return demand, cmat, cap, assign, mig_s, cost0

    # -- vectorized planner (the production path) -------------------------

    def plan(self, demand, state_gb: float = 1.0,
             initial=None, faults=None) -> PlacementPlan:
        """(N, R)-vectorized plan; bit-compatible with `plan_scalar`.

        `faults` (a `repro.robustness.FaultPlan`) injects seeded
        migration failures: a failed attempt pays the full stop-and-copy
        cost (overhead grams + downtime) but the container stays put,
        then waits `min(backoff_base * 2**(k-1), backoff_cap)` epochs
        after its k-th consecutive failure before becoming eligible
        again (capped exponential backoff). A successful move resets
        the failure streak. Failed attempts land in
        `PlacementPlan.failed_migrations`.
        """
        from repro.robustness.faults import migration_failure_mask
        demand, cmat, cap, assign, mig_s, cost0 = self._prep(
            demand, state_gb, initial)
        T, N = demand.shape
        R = self.n_regions
        t = self.tables
        b = t.baseline_idx
        base_b = float(t.base_w[b])
        span_b = float(t.peak_w[b]) - base_b
        mult_b = float(t.multiple[b])
        h_hr = self.config.horizon_intervals * self.interval_s / 3600.0
        hk = 1.0 + self.config.hysteresis
        min_dwell = self.config.min_dwell
        fail_mat = migration_failure_mask(faults, T, N)
        if fail_mat is not None:
            bb = int(faults.migration.backoff_base)
            bc = int(faults.migration.backoff_cap)
            fail_cnt = np.zeros(N, dtype=np.int64)
            retry_at = np.zeros(N, dtype=np.int64)
        failed_migrations = (np.zeros(N, dtype=np.int64)
                             if fail_mat is not None else None)

        dwell = np.full(N, 10 ** 6, dtype=np.int64)   # first move is free
        migrations = np.zeros(N, dtype=np.int64)
        overhead_g = np.zeros(N, dtype=np.float64)
        downtime_s = np.zeros(N, dtype=np.float64)
        assign_mat = np.empty((T, N), dtype=np.int64)
        assign0 = assign.copy()
        occ = np.bincount(assign, minlength=R) if cap is not None else None
        rows = np.arange(N)

        for n in range(T):
            c_row = cmat[n]                            # (R,)
            p_est = base_b + span_b * np.minimum(demand[n] / mult_b, 1.0)
            c_cur = c_row[assign]                      # (N,)
            save = (p_est[:, None] * (c_cur[:, None] - c_row[None, :])
                    / 1000.0 * h_hr)
            cost = (cost0[:, None] * (0.5 * (c_cur[:, None] + c_row[None, :]))
                    / 1000.0)
            net = save - hk * cost                     # (N, R)
            eligible = dwell >= min_dwell
            if fail_mat is not None:
                eligible = eligible & (n >= retry_at)
            dst = np.full(N, -1, dtype=np.int64)

            if cap is None:
                best = np.argmax(net, axis=1)
                net_best = net[rows, best]
                m = eligible & (net_best > 0.0) & (best != assign)
                np.copyto(dst, best, where=m)
            else:
                # preference rounds: a denied choice is struck and the
                # container falls through to its next positive-net region
                remaining = cap - occ
                for _ in range(R):
                    best = np.argmax(net, axis=1)
                    net_best = net[rows, best]
                    want = (eligible & (dst < 0) & (net_best > 0.0)
                            & (best != assign))
                    if not np.count_nonzero(want):
                        break
                    denied_any = False
                    for r in range(R):
                        m = want & (best == r)
                        cnt = np.count_nonzero(m)
                        if not cnt:
                            continue
                        if remaining[r] <= 0:
                            net[m, r] = -np.inf
                            denied_any = True
                            continue
                        adm = m & (np.cumsum(m) <= remaining[r])
                        n_adm = np.count_nonzero(adm)
                        remaining[r] -= n_adm
                        dst[adm] = r
                        if n_adm < cnt:
                            net[m & ~adm, r] = -np.inf
                            denied_any = True
                    if not denied_any:
                        break

            attempted = dst >= 0
            if fail_mat is None:
                moved = attempted
            else:
                failed = attempted & fail_mat[n]
                moved = attempted & ~failed
            if np.count_nonzero(attempted):
                # every attempt — failed or not — pays stop-and-copy:
                # the container was checkpointed and (partially) copied
                # before the destination rejected it
                src = assign[attempted]
                dst_a = dst[attempted]
                overhead_g[attempted] += (cost0[attempted]
                                          * (0.5 * (c_row[src]
                                                    + c_row[dst_a]))
                                          / 1000.0)
                downtime_s[attempted] += mig_s[attempted]
                migrations[moved] += 1
                if fail_mat is not None:
                    failed_migrations[failed] += 1
                    fail_cnt[failed] += 1
                    fail_cnt[moved] = 0
                    if np.count_nonzero(failed):
                        k = np.minimum(fail_cnt[failed] - 1, 20)
                        retry_at[failed] = n + 1 + np.minimum(
                            bb * (2 ** k), bc)
                if occ is not None and np.count_nonzero(moved):
                    np.subtract.at(occ, assign[moved], 1)
                    np.add.at(occ, dst[moved], 1)
                assign = np.where(moved, dst, assign)
            dwell += 1
            dwell[moved] = 0
            assign_mat[n] = assign

        return PlacementPlan(assign=assign_mat, migrations=migrations,
                             overhead_g=overhead_g, downtime_s=downtime_s,
                             region_intensity=cmat,
                             region_names=self.region_names,
                             initial=assign0,
                             failed_migrations=failed_migrations)

    # -- greedy scalar reference (parity oracle) --------------------------

    def plan_scalar(self, demand, state_gb: float = 1.0,
                    initial=None, faults=None) -> PlacementPlan:
        """Pure-Python greedy reference; every float expression mirrors
        `plan` term-for-term, so the two agree bit-for-bit (including
        the migration-failure + capped-backoff retry state)."""
        from repro.robustness.faults import migration_failure_mask
        demand, cmat, cap, assign0, mig_s, cost0 = self._prep(
            demand, state_gb, initial)
        T, N = demand.shape
        R = self.n_regions
        t = self.tables
        b = t.baseline_idx
        base_b = float(t.base_w[b])
        span_b = float(t.peak_w[b]) - base_b
        mult_b = float(t.multiple[b])
        h_hr = self.config.horizon_intervals * self.interval_s / 3600.0
        hk = 1.0 + self.config.hysteresis
        min_dwell = self.config.min_dwell

        assign = [int(a) for a in assign0]
        dwell = [10 ** 6] * N
        migrations = np.zeros(N, dtype=np.int64)
        overhead_g = np.zeros(N, dtype=np.float64)
        downtime_s = np.zeros(N, dtype=np.float64)
        assign_mat = np.empty((T, N), dtype=np.int64)
        occ = ([int(x) for x in np.bincount(assign0, minlength=R)]
               if cap is not None else None)
        fail_mat = migration_failure_mask(faults, T, N)
        if fail_mat is not None:
            bb = int(faults.migration.backoff_base)
            bc = int(faults.migration.backoff_cap)
            fail_cnt = [0] * N
            retry_at = [0] * N
        failed_migrations = (np.zeros(N, dtype=np.int64)
                             if fail_mat is not None else None)

        for n in range(T):
            c_row = [float(x) for x in cmat[n]]
            # per-container nets are epoch-constant (moves apply at epoch
            # end), so compute the (N, R) table once, as `plan` does
            nets = []
            for i in range(N):
                a = assign[i]
                d = float(demand[n, i])
                u = d / mult_b
                if u > 1.0:
                    u = 1.0
                p_est = base_b + span_b * u
                c_a = c_row[a]
                row = []
                for r in range(R):
                    save = p_est * (c_a - c_row[r]) / 1000.0 * h_hr
                    cost = (float(cost0[i]) * (0.5 * (c_a + c_row[r]))
                            / 1000.0)
                    row.append(save - hk * cost)
                nets.append(row)
            dst = [-1] * N
            remaining = ([int(cap[r]) - occ[r] for r in range(R)]
                         if occ is not None else None)
            rounds = R if remaining is not None else 1
            for _ in range(rounds):
                any_want = False
                denied_any = False
                # argmax snapshot at round start: strikes this round only
                # touch a container's own row, after its own argmax
                for i in range(N):
                    if dst[i] >= 0 or dwell[i] < min_dwell:
                        continue
                    if fail_mat is not None and n < retry_at[i]:
                        continue               # backing off after a failure
                    row = nets[i]
                    best, net_best = 0, row[0]
                    for r in range(1, R):
                        if row[r] > net_best:
                            best, net_best = r, row[r]
                    if not (net_best > 0.0 and best != assign[i]):
                        continue
                    any_want = True
                    if remaining is not None:
                        if remaining[best] <= 0:
                            row[best] = -np.inf       # fall through next round
                            denied_any = True
                            continue
                        remaining[best] -= 1
                    dst[i] = best
                if not any_want or not denied_any:
                    break
            moved = [False] * N
            for i in range(N):
                if dst[i] < 0:
                    continue
                a = assign[i]
                # every attempt pays stop-and-copy, failed or not
                overhead_g[i] += (float(cost0[i])
                                  * (0.5 * (c_row[a] + c_row[dst[i]]))
                                  / 1000.0)
                downtime_s[i] += float(mig_s[i])
                if fail_mat is not None and fail_mat[n, i]:
                    failed_migrations[i] += 1
                    fail_cnt[i] += 1
                    k = min(fail_cnt[i] - 1, 20)
                    retry_at[i] = n + 1 + min(bb * (2 ** k), bc)
                    continue                   # pays the cost, stays put
                migrations[i] += 1
                if fail_mat is not None:
                    fail_cnt[i] = 0
                if occ is not None:
                    occ[a] -= 1
                    occ[dst[i]] += 1
                assign[i] = dst[i]
                moved[i] = True
            for i in range(N):
                dwell[i] = 0 if moved[i] else dwell[i] + 1
            assign_mat[n] = assign

        return PlacementPlan(assign=assign_mat, migrations=migrations,
                             overhead_g=overhead_g, downtime_s=downtime_s,
                             region_intensity=cmat,
                             region_names=self.region_names,
                             initial=assign0.copy(),
                             failed_migrations=failed_migrations)

    # -- placed fleet runs -------------------------------------------------

    def run(self, policy, demand, targets, epsilon: float = 0.05,
            state_gb=1.0, demand_scale=1.0, initial=None,
            record: bool = False, plan: Optional[PlacementPlan] = None,
            compare_static: bool = False) -> PlacementResult:
        """Plan placement, then advance the fleet on the planned regions.

        `plan` reuses a precomputed `PlacementPlan` (must come from this
        engine's `plan`/`plan_scalar` on the same scaled demand) instead
        of re-planning. With `compare_static=True` the same fleet is
        also run frozen on the plan's own initial assignment (the
        no-migration baseline), populating
        `PlacementResult.saving_vs_static_pct`.
        """
        from repro.core.fleet import FleetSimulator
        demand = np.asarray(demand, dtype=np.float64)
        if demand.ndim == 1:
            demand = demand[:, None]
        scaled = demand
        if demand_scale is not None and np.any(
                np.asarray(demand_scale) != 1.0):
            scaled = demand * demand_scale
        if plan is None:
            plan = self.plan(scaled, state_gb=state_gb, initial=initial)
        elif plan.assign.shape != scaled.shape:
            raise ValueError(f"plan covers {plan.assign.shape}, demand is "
                             f"{scaled.shape}")
        sim = FleetSimulator(self.family, interval_s=self.interval_s,
                             migration=self.mig)
        fleet = sim.run(policy, scaled, plan.carbon_matrix(), targets,
                        epsilon=epsilon, state_gb=state_gb, record=record)
        static_fleet = None
        if compare_static:
            # baseline from the plan's own initial assignment, so a
            # precomputed plan compares against the start it was built on
            cmat = plan.region_intensity[:, plan.initial]
            static_fleet = sim.run(policy, scaled, cmat, targets,
                                   epsilon=epsilon, state_gb=state_gb,
                                   record=record)
        return PlacementResult(plan=plan, fleet=fleet,
                               static_fleet=static_fleet)
