"""JAX backend for the fleet simulator: jit/scan sweeps, device-resident.

`repro.core.fleet.FleetSimulator` advances the whole (N,) fleet per
monitoring interval with NumPy array state, but every epoch still
round-trips through the Python interpreter (~100 array-op dispatches per
step), which caps sweeps at low-thousands of containers. This module
ports the decision kernels and the epoch loop to JAX:

  - each policy's `decide_batch` masking scheme becomes a pure function
    on (N,) arrays, mirroring the NumPy kernels term-for-term;
  - the epoch loop becomes one `jax.lax.scan` over time with the whole
    fleet state as the carry, so a full run compiles to a single XLA
    computation with no per-step Python dispatch;
  - everything runs float64 (`jax.enable_x64`, scoped so
    the f32 model/kernel suites are untouched) and device-resident: one
    host->device push of the inputs, one device->host pull of the final
    state. In a placed sweep the region codes and the demand are already
    on the device, left there by the JAX planner, and the scan takes
    them up without a round trip through the host; only the small inputs
    go up (the (T, R) region tables, the (N,) targets, epsilon and state).

Branchy NumPy fast paths (`if np.count_nonzero(...)` gates, the
compacted `_best_fit_up_batch` walk, the closed-form dispatch for
state-free policies) are pure optimizations — executing the gated block
with an all-False mask is a no-op — so the scan step simply evaluates
every branch masked. The three `dwell` update branches in the NumPy loop
likewise collapse to one rule: dwell += ((kind >= 0) & (kind !=
K_MIGRATE)) after the migration-done reset. Clamps the NumPy path keeps
but documents as identities (duty and utilization already lie in [0, 1])
are elided.

XLA:CPU performance notes (measured via the fleet_sweep_jax benchmark):
XLA's CPU pipeline has no multi-output loop fusion, so a value consumed
by k downstream fusion roots gets its whole producer chain *duplicated*
k times — a naive port of the step (one big chain feeding ~15 carry
outputs) re-evaluates the entire decision cascade per output and runs
slower than NumPy. Gathers fare no better: a slice-table gather inside
the decision chain fragments the surrounding fusion and costs ~20x a
fused select. Three techniques recover the speedup:

  - static LUTs (`_lutf`/`_luti`): the slice family is tiny and static,
    so every table lookup compiles to a select chain over per-slice
    literals — fully fusible and SIMD-friendly, no gathers anywhere;
  - `_pack` stage boundaries: `optimization_barrier` around a row-stack
    force-materializes shared intermediates (the barrier stops
    slice-of-concat forwarding and is itself stripped late, leaving a
    plain materialized buffer); downstream fusions read rows instead of
    recomputing chains;
  - packed carry: the scan carry is three arrays (f64 accumulators +
    f64 dynamics + i32 state) instead of ~14, and all accumulator
    updates land in a single stacked add, keeping the number of fusion
    roots — and hence chain duplication — small.

Results come back as the same `FleetResult` dataclass; parity against
the NumPy backend is pinned to 1e-6 by `tests/test_fleet_jax.py` (and
the NumPy backend stays pinned to the scalar loop at 1e-9, anchoring
the chain).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence

import numpy as np

from repro import obs
from repro.cluster.migration import MigrationCostModel
from repro.cluster.slices import SliceFamily
from repro.core.fleet import (FleetResult, _aggregate_sweep_rows,
                              _elastic_budget_series, _prepare_energy,
                              _prepare_run_inputs, _prepare_sweep_inputs,
                              _prepare_traffic, _PEAK_WINDOW)
from repro.core.policy import K_MIGRATE, K_RESUME, K_STAY, K_SUSPEND
from repro.core.simulator import SimConfig

import jax
import jax.numpy as jnp
from jax import lax

# rows of the packed scan carry (see _fleet_scan): acc carries the four
# raw f64 sums, dyni carries i32 state then interval counters
_ACC_ROWS = 4            # sum(power*c), sum(power), sum(served), sum(thr)
_I_SLICE, _I_MT, _I_DWELL, _I_MIGS, _I_SUS, _I_SUSCNT = range(6)
_MIN_SHARD_COLS = 1024   # don't shard fleets smaller than this per device


# CPU-tuned XLA flags: multiple host devices let `FleetSimulatorJax.run`
# shard the container axis across cores (shards double as cache blocks,
# so more shards than cores still helps large fleets). They only affect
# the CPU platform; on an accelerator the real devices are used.
_CPU_XLA_FLAGS = ("--xla_force_host_platform_device_count=4",)


def ensure_cpu_xla_flags():
    """Append the CPU-tuned XLA flags to XLA_FLAGS unless the caller
    already set them (explicit user settings win). Must run before the
    first XLA backend initialization — i.e. before any jax computation,
    not necessarily before `import jax` — to take effect. The benchmark
    harness and the `--jax-sweep` demo call this; library users export
    the flags themselves (see README "Backends")."""
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    for f in _CPU_XLA_FLAGS:
        if f.split("=")[0] not in flags:
            flags = (flags + " " + f).strip()
    os.environ["XLA_FLAGS"] = flags


def shard_count(n_devices: int, N: int, n_rep: Optional[int] = None) -> int:
    """How many devices `FleetSimulatorJax.run` splits an N-container
    fleet over: at most one shard per device and per `_MIN_SHARD_COLS`
    containers, and for an indexed (rep-tiled) run at most one per rep
    block."""
    if n_rep is None:
        return max(1, min(n_devices, N // _MIN_SHARD_COLS))
    return max(1, min(n_devices, n_rep, N // _MIN_SHARD_COLS or 1))


class _TablesS(NamedTuple):
    """FamilyTables as a hashable constant (jit static arg): per-slice
    values become Python tuples so every lookup compiles to a select
    chain over literals instead of a fusion-breaking gather."""
    base_w: tuple
    peak_w: tuple
    multiple: tuple
    bw_gbps: tuple
    next_smaller: tuple
    next_larger: tuple
    n_slices: int
    smallest: int
    baseline_idx: int
    well_formed: bool

    @classmethod
    def from_tables(cls, t) -> "_TablesS":
        return cls(base_w=tuple(float(x) for x in t.base_w),
                   peak_w=tuple(float(x) for x in t.peak_w),
                   multiple=tuple(float(x) for x in t.multiple),
                   bw_gbps=tuple(float(x) for x in t.bw_gbps),
                   next_smaller=tuple(int(x) for x in t.next_smaller),
                   next_larger=tuple(int(x) for x in t.next_larger),
                   n_slices=len(t.multiple),
                   smallest=int(t.smallest),
                   baseline_idx=int(t.baseline_idx),
                   well_formed=bool(t.well_formed))


def _lutf(vals: tuple, idx):
    """Float table lookup as a select chain over literals (`idx` must
    already be clamped into range)."""
    out = jnp.full(idx.shape, vals[0], dtype=jnp.float64)
    for s in range(1, len(vals)):
        out = jnp.where(idx == s, vals[s], out)
    return out


def _luti(vals: tuple, idx):
    """Integer table lookup as a select chain over literals."""
    out = jnp.full(idx.shape, vals[0], dtype=jnp.int32)
    for s in range(1, len(vals)):
        out = jnp.where(idx == s, vals[s], out)
    return out


def _pack(*rows):
    """Force-materialize a group of same-dtype (N,) rows as one (R, N)
    buffer. The `optimization_barrier` keeps algebraic simplification
    from forwarding `pack[r]` back to the un-materialized producer; XLA
    strips the barrier itself after that, so what remains is a plain
    concatenate fusion evaluated once. Consumers index rows instead of
    re-deriving them (XLA:CPU would otherwise clone the whole producer
    chain into every consumer fusion)."""
    return lax.optimization_barrier(jnp.stack(rows))


# ---------------------------------------------------------------------------
# Decision kernels (staged ports of the policies' decide_batch)
# ---------------------------------------------------------------------------

def _policy_spec(policy) -> tuple:
    """Hashable kernel spec for a policy instance (jit cache key)."""
    from repro.core.policy import (CarbonAgnosticPolicy,
                                   CarbonContainerPolicy,
                                   SuspendResumePolicy)
    if type(policy) is CarbonAgnosticPolicy:
        return ("agnostic",)
    if type(policy) is SuspendResumePolicy:
        return ("suspend_resume",)
    if type(policy) is CarbonContainerPolicy:
        return ("cc", policy.variant, bool(policy.allow_migration),
                int(policy.min_dwell), float(policy.idle_margin))
    raise TypeError(
        f"backend='jax' has no decision kernel for {type(policy).__name__}; "
        f"stock policies only (use backend='fleet' for custom policies)")


def _nl_chain(tabs: _TablesS, i: int) -> list:
    """Static next-larger chain upward from slice i (exclusive)."""
    chain = []
    k = tabs.next_larger[i]
    while k >= 0:
        chain.append(k)
        k = tabs.next_larger[k]
    return chain


def _best_fit_up_j(tabs: _TablesS, i0, demand, budget):
    """`_best_fit_up_batch`, statically unrolled: the walk's visit order
    is a compile-time property of the slice family, so per-slice
    fit/serve predicates are computed once against literals and the
    per-start-slice outcome is a nested select — no table lookups at
    all. Runs full-width (no `active0` compaction): the walk has no side
    effects, so callers mask its result (`k_up >= 0` only consulted
    where the scalar path would have walked)."""
    S = tabs.n_slices
    # per-slice predicates against literals (shared by every chain)
    fits = []
    geq = []
    for s in range(S):
        u_s = jnp.minimum(demand / tabs.multiple[s], 1.0)
        pw_s = (tabs.base_w[s]
                + (tabs.peak_w[s] - tabs.base_w[s]) * u_s)
        fits.append(pw_s <= budget)
        geq.append(demand <= tabs.multiple[s])
    res = jnp.full(demand.shape, -1, dtype=jnp.int32)
    for i in range(S):
        chain = _nl_chain(tabs, i)
        if not chain:
            continue
        # walk outcome from start i, built from the chain's end backward:
        # at k: not fits -> -1; fits and (serves | last) -> k; else next
        last = chain[-1]
        r = jnp.where(fits[last], last, -1)
        for k in reversed(chain[:-1]):
            r = jnp.where(fits[k], jnp.where(geq[k], k, r), -1)
        res = jnp.where(i0 == i, r, res)
    return res.astype(jnp.int32)


def _decide_cc(spec, tabs, i0, sus, dwell, peak_r, d, c, budget):
    """CarbonContainerPolicy.decide_batch, staged.

    Mask priority == scalar control flow, exactly as the NumPy kernel
    (whose `decided` bookkeeping resolves to the disjoint branch masks
    used here). Shared float quantities and the expensive branch masks
    are `_pack`-materialized so the kind/duty/target select chains stay
    shallow.
    """
    _, variant, can_mig, min_dwell, idle_margin = spec
    base_i = _lutf(tabs.base_w, i0)
    peak_i = _lutf(tabs.peak_w, i0)
    mult_i = _lutf(tabs.multiple, i0)
    ns = _luti(tabs.next_smaller, i0)
    has_j = ns >= 0
    jj = jnp.maximum(ns, 0)
    base_j = _lutf(tabs.base_w, jj)
    peak_j = _lutf(tabs.peak_w, jj)
    mult_j = _lutf(tabs.multiple, jj)
    span_i = peak_i - base_i
    span_j = peak_j - base_j

    # --- stage 1: shared float quantities --------------------------------
    u_cap_i = jnp.minimum(1.0, (budget - base_i) / span_i)
    if not tabs.well_formed:
        u_cap_i = jnp.where(peak_i <= base_i, 1.0, u_cap_i)
    u_cap_i = jnp.where(budget <= base_i, 0.0, u_cap_i)
    u_cap_j = jnp.minimum(1.0, (budget - base_j) / span_j)
    if not tabs.well_formed:
        u_cap_j = jnp.where(peak_j <= base_j, 1.0, u_cap_j)
    u_cap_j = jnp.where(budget <= base_j, 0.0, u_cap_j)
    u_need_i = jnp.minimum(d / mult_i, 1.0)
    b_j0 = tabs.base_w[tabs.smallest]
    p_j0 = tabs.peak_w[tabs.smallest]
    u_cap_j0 = jnp.minimum(1.0, (budget - b_j0) / (p_j0 - b_j0))
    if not tabs.well_formed:
        u_cap_j0 = jnp.where(p_j0 <= b_j0, 1.0, u_cap_j0)
    u_cap_j0 = jnp.where(budget <= b_j0, 0.0, u_cap_j0)
    pw_need_i = base_i + span_i * u_need_i
    # materialize every LUT-bearing quantity the mask and duty chains
    # read more than once (a re-evaluated chain re-evaluates its LUTs)
    f1 = _pack(u_cap_i, u_cap_j, u_need_i, u_cap_j0, mult_i, mult_j,
               base_j, peak_j, pw_need_i, base_i, span_i)
    (u_cap_i, u_cap_j, u_need_i, u_cap_j0, mult_i, mult_j, base_j,
     peak_j, pw_need_i, base_i, span_i) = (f1[r] for r in range(11))
    span_j = peak_j - base_j

    if variant == "energy" and can_mig:
        k_up = _best_fit_up_j(tabs, i0, d, budget)

    # --- stage 2: branch masks, in scalar return order -------------------
    resume_ok = sus & (b_j0 <= budget) & (u_cap_j0 > 0.0)
    base_over = base_i > budget
    over = (pw_need_i > budget) | base_over
    hard = over & (base_over | (u_cap_i <= 0.0)) & ~sus
    soft = over & ~hard & ~sus
    if can_mig:
        # soft: emissions/throttle comparison on the next-smaller slice
        q_new = u_cap_i
        throttle_i = jnp.maximum(0.0, d - mult_i * q_new)
        u_qi = jnp.minimum(q_new, u_need_i)
        c_i = (base_i + span_i * u_qi) * c / 1000.0
        u_j = jnp.minimum(jnp.minimum(d / mult_j, u_cap_j), 1.0)
        throttle_j = jnp.maximum(0.0, d - mult_j * u_j)
        c_j = (base_j + span_j * u_j) * c / 1000.0
        s1 = (soft & has_j & (c_j < c_i)
              & (throttle_j <= throttle_i + 1e-12))
    else:
        s1 = jnp.zeros(d.shape, dtype=bool)
    below = ~over & ~sus
    if variant == "energy":
        if can_mig:
            can_idle = dwell >= min_dwell
            peak = jnp.maximum(peak_r, d)
            u_jp = peak / mult_j
            pw_jp = base_j + span_j * jnp.minimum(u_jp, 1.0)
            e1 = (below & can_idle & has_j
                  & (u_jp <= jnp.minimum(u_cap_j, 0.9))
                  & (pw_jp < (1.0 - idle_margin) * pw_need_i))
            throttled = below & ~e1 & (d > mult_i * u_cap_i)
            m1 = _pack(*(m.astype(jnp.int32)
                         for m in (resume_ok, hard, soft, s1, e1,
                                   throttled, has_j)),
                       k_up, jj)
            resume_ok, hard, soft, s1, e1, throttled, has_j = (
                m1[r] > 0 for r in range(7))
            k_up = m1[7]
            jj = m1[8]
            e2 = throttled & (k_up >= 0)
        else:
            e1 = e2 = jnp.zeros(d.shape, dtype=bool)
            m1 = _pack(resume_ok, hard, soft, s1)
            resume_ok, hard, soft, s1 = (m1[r] for r in range(4))
        # (the ~can_mig cascade below never reads jj/has_j)
    else:
        if can_mig:
            # performance: climb next-larger while the candidate fits
            # 0.9x budget — statically unrolled like _best_fit_up_j;
            # `k_idx` tracks the last accepted slice (the scalar loop's
            # `k`), `k_is_set` <=> k != i
            climbing = below & (dwell >= min_dwell)
            ok = []
            for s in range(tabs.n_slices):
                u_n = jnp.minimum(d / tabs.multiple[s], 1.0)
                pw_n = (tabs.base_w[s]
                        + (tabs.peak_w[s] - tabs.base_w[s]) * u_n)
                ok.append(pw_n <= 0.9 * budget)
            k_is_set = jnp.zeros(d.shape, dtype=bool)
            k_idx = jnp.zeros(d.shape, dtype=jnp.int32)
            for i in range(tabs.n_slices):
                chain = _nl_chain(tabs, i)
                if not chain:
                    continue
                reach = climbing
                k_i = jnp.full(d.shape, -1, dtype=jnp.int32)
                for s in chain:
                    reach = reach & ok[s]
                    k_i = jnp.where(reach, s, k_i)
                here = i0 == i
                k_idx = jnp.where(here & (k_i >= 0), k_i, k_idx)
                k_is_set = k_is_set | (here & (k_i >= 0))
            p1 = below & k_is_set
        else:
            p1 = jnp.zeros(d.shape, dtype=bool)
            k_idx = jnp.zeros(d.shape, dtype=jnp.int32)
        m1 = _pack(*(m.astype(jnp.int32)
                     for m in (resume_ok, hard, soft, s1, p1, has_j)),
                   k_idx, jj)
        resume_ok, hard, soft, s1, p1, has_j = (m1[r] > 0
                                                for r in range(6))
        k_idx = m1[6]
        jj = m1[7]

    # --- stage 3: kind / duty / target from materialized masks -----------
    kind = jnp.full(d.shape, K_STAY, dtype=jnp.int32)
    duty = jnp.zeros(d.shape, dtype=jnp.float64)
    tgt = jnp.full(d.shape, -1, dtype=jnp.int32)
    kind = jnp.where(resume_ok, K_RESUME, kind)
    kind = jnp.where(sus & ~resume_ok, K_SUSPEND, kind)
    duty = jnp.where(resume_ok, u_cap_j0, duty)
    tgt = jnp.where(resume_ok, tabs.smallest, tgt)
    if can_mig:
        h1 = hard & has_j & (base_j <= budget)
        h_mig = hard & has_j
        h3 = hard & ~has_j & (i0 == tabs.smallest)
        kind = jnp.where(h_mig, K_MIGRATE, kind)
        kind = jnp.where(h3, K_SUSPEND, kind)
        duty = jnp.where(h1, u_cap_j, duty)
        tgt = jnp.where(h_mig, jj, tgt)
        kind = jnp.where(s1, K_MIGRATE, kind)
        duty = jnp.where(s1, u_cap_j, duty)
        tgt = jnp.where(s1, jj, tgt)
    else:
        kind = jnp.where(hard, K_SUSPEND, kind)
    duty = jnp.where(soft & ~s1, u_cap_i, duty)        # stay at q_new
    if variant == "energy":
        rest = ~sus & ~hard & ~soft
        if can_mig:
            kind = jnp.where(e1 | e2, K_MIGRATE, kind)
            duty = jnp.where(e1, u_cap_j, duty)
            duty = jnp.where(e2, 1.0, duty)
            tgt = jnp.where(e1, jj, tgt)
            tgt = jnp.where(e2, k_up, tgt)
            rest = rest & ~e1 & ~e2
        duty = jnp.where(rest, u_cap_i, duty)
    else:
        rest = ~sus & ~hard & ~soft
        kind = jnp.where(p1, K_MIGRATE, kind)
        duty = jnp.where(p1, 1.0, duty)
        tgt = jnp.where(p1, k_idx, tgt)
        duty = jnp.where(rest & ~p1, u_cap_i, duty)
    return kind, duty, tgt


def _decide_sr(spec, tabs, i0, sus, dwell, peak, d, c, budget):
    b = tabs.baseline_idx
    base_b = tabs.base_w[b]
    span_b = tabs.peak_w[b] - base_b
    u = jnp.minimum(d / tabs.multiple[b], 1.0)
    pw = base_b + span_b * u
    # over <=> rate(power) > (1-eps)*target; the hoisted SR budget row
    # carries the (1-eps)*target rate threshold (see _fleet_scan)
    over = pw * c / 1000.0 > budget
    kind = jnp.where(over, K_SUSPEND,
                     jnp.where(sus, K_RESUME, K_STAY)).astype(jnp.int32)
    duty = jnp.ones(d.shape, dtype=jnp.float64)
    tgt = jnp.where(kind == K_RESUME, b, -1).astype(jnp.int32)
    return kind, duty, tgt


def _decide_agnostic(spec, tabs, i0, sus, dwell, peak, d, c, budget):
    # baseline server: migrate back if ever off the baseline slice
    off_base = i0 != tabs.baseline_idx
    kind = jnp.where(off_base, K_MIGRATE, K_STAY).astype(jnp.int32)
    duty = jnp.ones(d.shape, dtype=jnp.float64)
    tgt = jnp.where(off_base, tabs.baseline_idx, -1).astype(jnp.int32)
    return kind, duty, tgt


_DECIDERS = {"agnostic": _decide_agnostic, "suspend_resume": _decide_sr,
             "cc": _decide_cc}


# ---------------------------------------------------------------------------
# The scan: whole (N,) fleet state as the carry, one step per epoch
# ---------------------------------------------------------------------------

@partial(jax.jit,
         static_argnames=("spec", "srs", "record", "tabs", "dt", "mig",
                          "cmode", "n_rep", "R", "traffic", "energy"))
def _fleet_scan(demand, cmat, targets, eps, state_gb, req_mat=None,
                solar_mat=None, up_mat=None, obs_mat=None, gap_vec=None, *,
                spec: tuple, srs: bool, record: bool, tabs: _TablesS,
                dt: float, mig: tuple, cmode: str = "dense", n_rep: int = 1,
                R: int = 0, traffic=None, energy=None):
    """One XLA computation: scan the staged epoch step over time.

    The carry is three packed arrays — f64 accumulators (6 + S + 1 rows:
    emissions, energy, work, throttled, demand, suspended_s, then
    time-on-slice columns), f64 dynamics (duty, migrating_s), and i32
    state (slice, migrate_target, dwell, migrations, suspended) — so the
    step has few fusion roots (see module docstring).

    Scale hardening (the N=1M placed sweep): nothing (T, N)-shaped is
    hoisted. The per-interval power budgets and the rolling
    _PEAK_WINDOW demand max — previously precomputed as (T, N)
    matrices, 2.3 GB each at N=1M/T=288 f64 — are computed inside the
    step (the budget is elementwise in the epoch's carbon row; the peak
    reads a (W-1, N) demand-window carry). Both are the exact same
    float expressions as the hoisted forms, so backend parity is
    untouched.

    `cmode` selects the carbon layout: "dense" takes `cmat` as the
    (T,) or (T, N) intensity matrix; "indexed" takes `cmat` as a
    `(region_mat (T, R) f64, codes (T, n_cols) int32)` pair and derives
    each epoch's per-container intensity with an R-way select chain —
    at fleet scale the (T, N) f64 matrix becomes a (T, n_cols) int32
    code matrix. `n_rep > 1` (indexed mode only) tiles the compact
    demand/code columns n_rep times *inside the step*, for
    target-sweep fleets whose columns repeat the same traces: the
    logical fleet is N = n_cols * n_rep wide but only compact inputs
    ever exist on host or in HBM.

    `traffic` (a static `repro.traffic.sim_jax.TrafficSpec`; indexed
    mode only, with `req_mat` the (T, R) request tensor in xs) folds the
    traffic subsystem into the same scan: each step routes the epoch's
    request row by the carbon row, autoscales the per-region replica
    fleets (an (R,) replica-count carry), and modulates each compact
    demand column by its region's serving load before the n_rep tiling
    — all carries stay (R,)/(R, R)-shaped, nothing (T, N). A fifth
    accumulator row sums the modulated demand so `work_demanded` can be
    recovered without re-materializing it on host.

    `energy` (a static `repro.energy.supply.EnergySpec`; indexed mode
    only, with `solar_mat`/`up_mat` the (T, R) solar-generation and
    grid-up tensors in xs) folds the virtual energy supply into the
    same scan: each step sums the compact columns into the (R,)
    per-region flexible load, advances the battery state of charge (an
    (R,) carry) through `repro.energy.supply_jax.energy_step`, clamps
    each column's demand by its region's virtual-cap fraction, and
    swaps the carbon row for the delivered mix's effective intensity —
    all before the n_rep tiling, pinned after the traffic modulation
    (demand_scale -> traffic -> energy, same layer order as the fleet
    backend). Reuses the traffic path's extra accumulator row for
    `work_demanded`.

    `obs_mat` (optional xs tensor) splits the signal plane from the
    billing plane: decision kernels and their per-epoch power budgets
    consume the *observed* intensity row — (T, R) in indexed mode
    (selected through the same R-way chain, and with the energy fold
    scaled onto the delivered mix by the per-region observed/true
    ratio), (T,) or (T, N) dense — while emissions stay billed at the
    true feed. The traffic fold routes on the observed row too (the
    router is a controller). `gap_vec` (optional (T,) xs vector) marks
    power-telemetry outage epochs; an extra accumulator row sums the
    gap epochs' emissions (`unmetered_g`).

    Returns the final carry tuple (+ optional (T, N) power/served series).
    """
    if cmode == "indexed":
        region_mat, codes = cmat
        n_cols = demand.shape[1]
        N = n_cols * n_rep
    else:
        assert n_rep == 1, "n_rep tiling requires indexed carbon"
        assert traffic is None, "traffic fold requires indexed carbon"
        assert energy is None, "energy fold requires indexed carbon"
        N = demand.shape[1]
    if traffic is not None:
        from repro.traffic.sim_jax import traffic_step
    if energy is not None:
        from repro.energy.supply_jax import energy_step
    S = tabs.n_slices
    decide = _DECIDERS[spec[0]]
    suspend_r = spec[0] == "suspend_resume"
    (sb, spg, rb, rpg, cpg, dpg, ratio, default_bw, extra) = mig

    # only the energy variant's idle-migration rule reads the rolling
    # demand peak (ContainerState.recent_peak); others skip the window
    # carry entirely
    use_peak = spec[0] == "cc" and spec[1] == "energy" and spec[2]
    # SuspendResumePolicy compares emission rates: its (epoch-invariant)
    # budget is the (1-eps)*target rate threshold, hoisted once
    sr_budget = ((1.0 - eps) * targets if suspend_r
                 else jnp.zeros((), dtype=jnp.float64))

    has_obs = obs_mat is not None
    has_gap = gap_vec is not None
    tos_cols = jnp.arange(S + 1, dtype=jnp.int32)
    n_acc = (_ACC_ROWS
             + (1 if (traffic is not None or energy is not None) else 0)
             + (1 if has_gap else 0))
    acc0 = jnp.zeros((n_acc, N), dtype=jnp.float64)
    rep0 = (jnp.full(R, float(traffic.min_rep), dtype=jnp.float64)
            if traffic is not None else None)
    soc0 = (jnp.full(R, energy.soc0_wh, dtype=jnp.float64)
            if energy is not None else None)
    dynf0 = jnp.stack([jnp.ones(N, dtype=jnp.float64),       # duty
                       jnp.zeros(N, dtype=jnp.float64)])     # migrating_s
    dyni0 = jnp.concatenate(
        [jnp.stack([jnp.full(N, tabs.baseline_idx, dtype=jnp.int32),
                    jnp.full(N, -1, dtype=jnp.int32),    # migrate_target
                    jnp.full(N, 10 ** 6, dtype=jnp.int32),  # dwell
                    jnp.zeros(N, dtype=jnp.int32),       # migrations
                    jnp.zeros(N, dtype=jnp.int32)]),     # suspended
         # interval counters: suspended + per-slice occupancy (exact:
         # k * dt == dt summed k times for integral dt-multiples)
         jnp.zeros((S + 2, N), dtype=jnp.int32)])
    # zero-padded demand window (rolling peak includes the current
    # interval; exact because demand >= 0)
    win0 = (jnp.zeros((_PEAK_WINDOW - 1, N), dtype=jnp.float64)
            if use_peak else None)

    def step(st, x):
        if energy is not None:
            soc = st[-1]
            st = st[:-1]
        if traffic is not None:
            rep = st[-1]
            st = st[:-1]
        # observed-feed / gap xs ride at the tail: pop them first
        g = None
        if has_gap:
            g = x[-1]
            x = x[:-1]
        obs_row = None
        if has_obs:
            obs_row = x[-1]
            x = x[:-1]
        if cmode == "indexed":
            if energy is not None:
                sol_row, up_row = x[-2], x[-1]
                x = x[:-2]
            if traffic is not None:
                d, code, c_row, req = x
                with jax.named_scope("traffic"):
                    # route this epoch's requests by the carbon row, scale
                    # the replica fleets; the serving loads modulate demand
                    # (the router is a controller: it sees the observed feed)
                    rep1, t_outs = traffic_step(
                        traffic, rep, req, obs_row if has_obs else c_row)
                    mod_row = t_outs[0]
                    mod = jnp.full(code.shape, mod_row[0], dtype=jnp.float64)
                    for r in range(1, R):
                        mod = jnp.where(code == r, mod_row[r], mod)
                    d = d * mod
            else:
                d, code, c_row = x
            if energy is not None:
                with jax.named_scope("energy"):
                    # virtual energy supply: the compact columns sum into
                    # the (R,) flexible-load row (linear in demand, see
                    # repro.energy.supply), one battery/solar/grid step
                    # advances the (R,) SoC carry, and the cap fraction +
                    # effective intensity come back through the same R-way
                    # selects as the carbon row
                    load_row = jnp.stack(
                        [jnp.sum(jnp.where(code == r, d, 0.0))
                         for r in range(R)]) * energy.load_coef
                    c_raw = c_row           # true grid row, pre-delivered-mix
                    soc1, e_outs = energy_step(energy, soc, load_row,
                                               sol_row, c_row, up_row)
                    cap_row, c_row = e_outs[5], e_outs[6]
                    if has_obs:
                        # the controller observes the delivered mix through
                        # the degraded feed: scale the effective intensity
                        # by the per-region observed/true grid ratio (same
                        # floats as the fleet backend's ceff_obs_reg)
                        raw_safe = jnp.where(c_raw > 0.0, c_raw, 1.0)
                        obs_row = c_row * jnp.where(
                            c_raw > 0.0, obs_row / raw_safe, 1.0)
                    capsel = jnp.full(code.shape, cap_row[0],
                                      dtype=jnp.float64)
                    for r in range(1, R):
                        capsel = jnp.where(code == r, cap_row[r], capsel)
                    d = d * capsel
            with jax.named_scope("signal"):
                # R-way select chain over the epoch's (R,) region row — the
                # compact-width analogue of gathering region_mat[t, codes[t]]
                c = jnp.full(code.shape, c_row[0], dtype=jnp.float64)
                for r in range(1, R):
                    c = jnp.where(code == r, c_row[r], c)
                if has_obs:
                    c_dec = jnp.full(code.shape, obs_row[0], dtype=jnp.float64)
                    for r in range(1, R):
                        c_dec = jnp.where(code == r, obs_row[r], c_dec)
                if n_rep > 1:
                    d = jnp.tile(d, n_rep)
                    c = jnp.tile(c, n_rep)
                    if has_obs:
                        c_dec = jnp.tile(c_dec, n_rep)
        else:
            d, c = x
            if has_obs:
                c_dec = obs_row
        with jax.named_scope("signal"):
            if not has_obs:
                c_dec = c
            if use_peak:
                acc, dynf, dyni, win = st
                peak = d
                for k in range(_PEAK_WINDOW - 1):
                    peak = jnp.maximum(peak, win[k])
                win1 = jnp.concatenate([win[1:], d[None, :]], axis=0)
            else:
                acc, dynf, dyni = st
                peak = jnp.zeros((), dtype=jnp.float64)
            # per-interval power budget (policy._budget_batch, elementwise
            # in the epoch's carbon values — same floats as the hoisted
            # (T, N) form)
            if spec[0] == "agnostic":
                budget = jnp.zeros((), dtype=jnp.float64)
            elif suspend_r:
                budget = sr_budget
            else:
                c_safe = jnp.where(c_dec <= 0.0, 1.0, c_dec)
                budget = jnp.where(c_dec <= 0.0, jnp.inf,
                                   (1.0 - eps) * targets * 1000.0 / c_safe)
        with jax.named_scope("decide"):
            i0 = dyni[_I_SLICE]
            mt0 = dyni[_I_MT]
            dwell0 = dyni[_I_DWELL]
            sus = dyni[_I_SUS] > 0
            duty0 = dynf[0]
            migr_s0 = dynf[1]
            migm = migr_s0 > 0.0

            kind, dy, tg = decide(spec, tabs, i0, sus, dwell0, peak, d, c_dec,
                                  budget)
            kind = jnp.where(migm, -1, kind)
            dstc = jnp.where(kind == K_MIGRATE, tg, 0)
            dstc_m = jnp.where(migm, mt0, 0)
            di = _pack(kind, tg, dstc, dstc_m)
            kind, tg, dstc, dstc_m = di[0], di[1], di[2], di[3]

            m_sus = kind == K_SUSPEND
            m_res = kind == K_RESUME
            m_stay = kind == K_STAY
            m_mig = kind == K_MIGRATE

            base_i = _lutf(tabs.base_w, i0)
            base_dm = _lutf(tabs.base_w, dstc_m)    # in-flight migration dst
            base_dst = _lutf(tabs.base_w, dstc)     # newly decided dst

            # stop-and-copy time (MigrationCostModel, same term order incl.
            # the zero-bandwidth fallback) + post-decision slice + duty
            bw = jnp.maximum(_lutf(tabs.bw_gbps, i0),
                             _lutf(tabs.bw_gbps, dstc))
            bw = jnp.where(bw == 0.0, default_bw, bw)
            mig_s = (sb + spg * state_gb) + (rb + rpg * state_gb)
            mig_s = mig_s + (cpg + dpg) * state_gb
            mig_s = mig_s + (state_gb / ratio) / bw
            mig_s = mig_s + extra
            duty1 = jnp.where(m_res | m_stay | m_mig, dy, duty0)
            pf = _pack(mig_s, duty1, base_i)
            mig_s, duty, base_i = pf[0], pf[1], pf[2]
            has_t = m_res & (tg >= 0)
            longm = m_mig & (mig_s >= dt)
            subm = m_mig & ~longm
            idx1 = jnp.where(subm | has_t, tg, i0)

        with jax.named_scope("plant"):
            # ---- plant step for running containers ----------------------
            mult_c = _lutf(tabs.multiple, idx1)
            base_c = _lutf(tabs.base_w, idx1)
            peak_c = _lutf(tabs.peak_w, idx1)
            cap = mult_c * duty             # duty in [0,1]: clamp elided
            srv = jnp.minimum(d, cap)
            util = srv / mult_c
            pw = base_c + (peak_c - base_c) * util
            down = jnp.minimum(mig_s, dt) / dt
            p_mig = base_i + base_dst
            full = m_res | m_stay
            power = jnp.where(migm, base_i + base_dm, 0.0)
            if not srs:
                power = jnp.where(m_sus, base_i, power)
            power = jnp.where(longm, p_mig, power)
            power = jnp.where(full, pw, power)
            power = jnp.where(subm, down * p_mig + (1.0 - down) * pw, power)
            served = jnp.where(full, srv, 0.0)
            served = jnp.where(subm, (1.0 - down) * srv, served)
            ps = _pack(power, served)
            power, served = ps[0], ps[1]

        with jax.named_scope("account"):
            # ---- fused accounting (scalar _account, reassociated) --------
            # accumulate raw per-step sums; the loop-invariant dt/3600/1000
            # scalings apply once after the scan. Time-on-slice and
            # suspended time are interval *counters* (i32) scaled by dt at
            # the end. Both reassociations shift results by ~1e-13 relative
            # — far inside the backend's 1e-6 parity budget.
            suspended1 = jnp.where(m_sus, True, sus)
            suspended1 = jnp.where(m_res, False, suspended1)
            tos_col = jnp.where(suspended1, S, idx1)
            rows = [power * c,                              # -> emissions_g
                    power,                                  # -> energy_wh
                    served,                                 # -> work_done
                    jnp.maximum(0.0, d - served)]           # -> throttled
            if traffic is not None or energy is not None:
                rows.append(d)                              # -> work_demanded
            if has_gap:
                # telemetry outage: emissions happen but the meter is blind
                rows.append(rows[0] * g)                    # -> unmetered_g
            contribs = jnp.stack(rows)
            acc1 = acc + contribs

        with jax.named_scope("migrate"):
            # ---- migration progress + dwell (after accounting) ----------
            migr1 = jnp.where(longm, mig_s - dt, migr_s0)
            migr2 = jnp.where(migm, migr1 - dt, migr1)
            done = migm & (migr2 <= 0.0)
            slice2 = jnp.where(done, mt0, idx1)
            mt1 = jnp.where(longm, tg, mt0)
            mt2 = jnp.where(done, -1, mt1)
            dwell1 = jnp.where(subm, 0, dwell0)
            dwell1 = jnp.where(done, 0, dwell1)
            dwell2 = dwell1 + ((kind >= 0) & (kind != K_MIGRATE))
            migs2 = dyni[_I_MIGS] + m_mig
            dynf1 = jnp.stack([duty, migr2])
            dyni1 = jnp.concatenate(
                [jnp.stack([slice2, mt2, dwell2, migs2,
                            suspended1.astype(jnp.int32),
                            dyni[_I_SUSCNT] + m_sus]),       # suspended count
                 dyni[_I_SUSCNT + 1:]
                 + (tos_col[None, :] == tos_cols[:, None])])
        ys = (power, served) if record else None
        st1 = ((acc1, dynf1, dyni1, win1) if use_peak
               else (acc1, dynf1, dyni1))
        if traffic is not None:
            st1 = st1 + (rep1,)
        if energy is not None:
            st1 = st1 + (soc1,)
        return st1, ys

    st0 = ((acc0, dynf0, dyni0, win0) if use_peak
           else (acc0, dynf0, dyni0))
    if traffic is not None:
        st0 = st0 + (rep0,)
    if energy is not None:
        st0 = st0 + (soc0,)
    if cmode == "indexed":
        xs = (demand, codes, region_mat)
        if traffic is not None:
            xs = xs + (req_mat,)
        if energy is not None:
            xs = xs + (solar_mat, up_mat)
    else:
        xs = (demand, cmat)
    if has_obs:
        xs = xs + (obs_mat,)
    if has_gap:
        xs = xs + (gap_vec,)
    carry, ys = lax.scan(step, st0, xs)
    return carry[:3], ys


class FleetSimulatorJax:
    """Drop-in JAX counterpart of `FleetSimulator`: same `run` signature
    (minus custom-policy support), same `FleetResult` out, one XLA
    computation per (policy, shape) pair. First call per signature
    compiles; steady-state calls are device-resident end-to-end."""

    def __init__(self, family: SliceFamily, interval_s: float = 300.0,
                 suspend_releases_slice: bool = True,
                 migration: Optional[MigrationCostModel] = None):
        self.family = family
        self.tables = family.tables()
        self.interval_s = float(interval_s)
        self.suspend_releases_slice = suspend_releases_slice
        self.mig = migration or MigrationCostModel()
        self._tabs = _TablesS.from_tables(self.tables)

    def _mig_spec(self) -> tuple:
        m = self.mig
        return (m.suspend_base_s, m.suspend_per_gb_s, m.resume_base_s,
                m.resume_per_gb_s, m.compress_per_gb_s,
                m.decompress_per_gb_s, m.compression_ratio,
                m.transfer_gbps, m.restore_extra_s)

    def run(self, policy, demand, carbon, targets, epsilon=0.05,
            state_gb=1.0, demand_scale=1.0, record: bool = False,
            n_rep: int = 1, traffic=None, energy=None,
            carbon_obs=None, power_gap=None,
            demand_device=None) -> FleetResult:
        """Advance the fleet; same contract as `FleetSimulator.run`, plus
        the memory-lean indexed-carbon form: `carbon` may be a
        ``(region_mat (T, R), codes (T, n_cols) int)`` pair — a
        placement plan's region-intensity table plus per-epoch region
        codes — in which case `demand` is the compact (T, n_cols)
        matrix and ``n_rep`` tiles its columns inside the scan step to
        the logical fleet width N = n_cols * n_rep (targets/epsilon/
        state_gb are full-N). No (T, N) array exists on host or device.
        The codes may be a device array (a `jax.Array`, such as the
        JAX planner's `PlacementPlan.assign_device`), and
        `demand_device` the device copy of the compact `demand`, pushed
        and checked already (`PlacementPlan.demand_device`): the run
        then takes them as they are, with no host cast, check or push,
        and reads the host `demand` only for `work_demanded`.

        `traffic` (indexed-carbon runs only) is a ``(TrafficSpec,
        req_mat (T, R))`` pair: the scan then also routes + autoscales
        the request tensor each epoch and modulates container demand by
        the per-region serving load (see `_fleet_scan`).

        `energy` (indexed-carbon runs only) is an ``(EnergySpec,
        solar_mat (T, R), grid_up (T, R))`` triple: the scan then also
        advances the virtual energy supply each epoch, clamping demand
        by the per-region virtual-cap fraction and billing emissions at
        the delivered mix's effective intensity (see `_fleet_scan`).

        `carbon_obs` splits the signal plane from the billing plane
        (see `_fleet_scan`): the policy decides — and budgets — on the
        observed intensity while emissions stay billed at `carbon`.
        Indexed runs take a (T, R) observed region matrix; dense runs a
        (T,) or (T, N) observed matrix. `power_gap` is a (T,) 0/1
        vector of power-telemetry outage epochs; the result then
        carries `unmetered_g`, the emissions accrued while the meter
        was blind.
        """
        with obs.span("fleet.prepare"):
            spec = _policy_spec(policy)
            t = self.tables
            dt = self.interval_s
            indexed = isinstance(carbon, tuple)
            if traffic is not None and not indexed:
                raise ValueError("traffic fold requires indexed carbon "
                                 "(region_mat, codes)")
            if energy is not None and not indexed:
                raise ValueError("energy fold requires indexed carbon "
                                 "(region_mat, codes)")
            if indexed:
                region_mat, codes = carbon
                demand = np.asarray(demand, dtype=np.float64)
                if demand.ndim != 2:
                    raise ValueError("indexed-carbon run needs (T, n_cols) "
                                     "demand")
                scaled = demand_scale is not None and np.any(
                    np.asarray(demand_scale) != 1.0)
                if demand_device is not None:
                    if scaled or demand_device.shape != demand.shape:
                        raise ValueError("the device demand must be a copy "
                                         "of the demand, unscaled")
                else:
                    if scaled:
                        demand = demand * demand_scale
                    if demand.size and demand.min() < 0.0:
                        raise ValueError("fleet demand must be "
                                         "non-negative")
                T, n_cols = demand.shape
                N = n_cols * int(n_rep)
                region_mat = np.asarray(region_mat, dtype=np.float64)
                if not isinstance(codes, jax.Array):
                    codes = np.asarray(codes, dtype=np.int32)
                if region_mat.ndim != 2 or region_mat.shape[0] != T:
                    raise ValueError(f"region matrix shape {region_mat.shape}"
                                     f" does not match demand (T={T})")
                if codes.shape != (T, n_cols):
                    raise ValueError(f"region codes shape {codes.shape} does "
                                     f"not match demand {(T, n_cols)}")
                R = region_mat.shape[1]
                t_spec = req_mat = None
                if traffic is not None:
                    t_spec, req_mat = traffic
                    req_mat = np.asarray(req_mat, dtype=np.float64)
                    if req_mat.shape != (T, R):
                        raise ValueError(f"traffic request tensor shape "
                                         f"{req_mat.shape}; expected {(T, R)}")
                e_spec = solar_mat = up_mat = None
                if energy is not None:
                    e_spec, solar_mat, up_mat = energy
                    solar_mat = np.asarray(solar_mat, dtype=np.float64)
                    up_mat = np.asarray(up_mat, dtype=np.float64)
                    if solar_mat.shape != (T, R) or up_mat.shape != (T, R):
                        raise ValueError(
                            f"energy solar/grid-up tensor shapes "
                            f"{solar_mat.shape} / {up_mat.shape}; expected "
                            f"{(T, R)}")
                targets = np.broadcast_to(
                    np.asarray(targets, dtype=np.float64), (N,))
                epsilon = np.broadcast_to(
                    np.asarray(epsilon, dtype=np.float64), (N,))
                state_gb = np.broadcast_to(
                    np.asarray(state_gb, dtype=np.float64), (N,))
            else:
                if n_rep != 1:
                    raise ValueError("n_rep tiling requires indexed carbon")
                if demand_device is not None:
                    raise ValueError("a device demand requires indexed "
                                     "carbon")
                (demand, cmat, targets, epsilon, state_gb, T, N) = \
                    _prepare_run_inputs(demand, carbon, targets, epsilon,
                                        state_gb, demand_scale, self.interval_s)
                R = 0
            if carbon_obs is not None:
                carbon_obs = np.asarray(carbon_obs, dtype=np.float64)
                if indexed:
                    if carbon_obs.shape != (T, R):
                        raise ValueError(f"observed carbon shape "
                                         f"{carbon_obs.shape}; indexed runs "
                                         f"need the (T, R) region form "
                                         f"{(T, R)}")
                elif carbon_obs.shape not in ((T,), (T, N)):
                    raise ValueError(f"observed carbon shape "
                                     f"{carbon_obs.shape} does not match "
                                     f"(T,)={T,} or (T, N)={(T, N)}")
            if power_gap is not None:
                power_gap = np.asarray(power_gap, dtype=np.float64)
                if power_gap.shape != (T,):
                    raise ValueError(f"power-gap vector shape "
                                     f"{power_gap.shape}; expected {(T,)}")

            # container-parallel sharding: containers never interact, so the
            # fleet splits into contiguous column shards dispatched to the
            # host's XLA devices (jax dispatch is async — shards execute
            # concurrently, one thread pool per device). Results concatenate
            # bit-identically to the unsharded run. Multiple host devices
            # come from XLA_FLAGS=--xla_force_host_platform_device_count=K.
            # Indexed runs shard over rep blocks (the compact columns are
            # shared, so column shards would re-push them per device
            # anyway).
            devices = jax.devices()
            n_sh = shard_count(len(devices), N,
                               int(n_rep) if indexed else None)
            kw = dict(spec=spec, srs=self.suspend_releases_slice,
                      record=record, tabs=self._tabs, dt=dt,
                      mig=self._mig_spec())
        with jax.enable_x64(True):
            with obs.span("fleet.h2d"):
                shards, statics = [], []
                for s in range(n_sh):
                    if indexed:
                        lo_r = s * n_rep // n_sh
                        hi_r = (s + 1) * n_rep // n_sh
                        lo, hi = lo_r * n_cols, hi_r * n_cols
                        args = (demand if demand_device is None
                                else demand_device, (region_mat, codes),
                                targets[lo:hi], epsilon[lo:hi],
                                state_gb[lo:hi], req_mat, solar_mat, up_mat,
                                carbon_obs, power_gap)
                        static = dict(cmode="indexed", n_rep=hi_r - lo_r,
                                      R=R, traffic=t_spec, energy=e_spec)
                    else:
                        lo = s * N // n_sh
                        hi = (s + 1) * N // n_sh
                        ob = carbon_obs
                        if carbon_obs is not None and carbon_obs.ndim == 2:
                            ob = carbon_obs[:, lo:hi]
                        args = (demand[:, lo:hi],
                                cmat if cmat.ndim == 1 else cmat[:, lo:hi],
                                targets[lo:hi], epsilon[lo:hi],
                                state_gb[lo:hi], None, None, None, ob,
                                power_gap)
                        static = {}
                    # device arrays are taken up where they are (or copied
                    # device to device to another shard's chip)
                    leaves = jax.tree_util.tree_leaves(args)
                    handed = obs.nbytes([x for x in leaves
                                         if isinstance(x, jax.Array)])
                    obs.count("handoff_bytes", handed)
                    obs.count("h2d_bytes", obs.nbytes(leaves) - handed)
                    shards.append(jax.device_put(args, devices[s]))
                    statics.append(static)
                # every shard's push is in flight before the one wait
                shards = jax.block_until_ready(shards)
            outs = [_fleet_scan(*args, **static, **kw)
                    for args, static in zip(shards, statics)]
            with obs.span("fleet.wait"):
                jax.block_until_ready(outs)
            with obs.span("fleet.d2h"):
                got = jax.device_get(
                    [(o[0][0], o[0][2], o[1] if record else None)
                     for o in outs])
                obs.count("d2h_bytes", obs.nbytes(got))
                acc = np.concatenate([g[0] for g in got], axis=1)
                dyni = np.concatenate([g[1] for g in got], axis=1)
                ys = None
                if record:
                    ys = tuple(np.concatenate([g[2][k] for g in got], axis=1)
                               for k in range(2))

        with obs.span("fleet.result"):
            elapsed = float(np.cumsum(np.full(T, dt))[-1]) if T else 0.0
            if traffic is not None or energy is not None:
                # host demand is pre-modulation/pre-cap: the scan's fifth
                # accumulator row carries the effective per-container sums
                work_dem = acc[_ACC_ROWS] * dt
            else:
                work_dem = demand.sum(axis=0) * dt
                if indexed and n_rep > 1:
                    work_dem = np.tile(work_dem, n_rep)
            # loop-invariant scalings deferred out of the scan (see
            # _fleet_scan's accounting note); term order mirrors _account
            return FleetResult(
                emissions_g=acc[0] / 1000.0 * dt / 3600.0,
                energy_wh=acc[1] * dt / 3600.0,
                work_done=acc[2] * dt,
                work_demanded=work_dem,
                throttled_integral=acc[3] * dt,
                migrations=dyni[_I_MIGS].astype(np.int64),
                suspended_s=dyni[_I_SUSCNT].astype(np.float64) * dt,
                elapsed_s=np.full(N, elapsed),
                time_on_slice_s=np.ascontiguousarray(
                    dyni[_I_SUSCNT + 1:].T.astype(np.float64)) * dt,
                slice_names=t.names + ("suspended",),
                baseline_cap=float(t.multiple[t.baseline_idx]),
                power_series=ys[0] if record else None,
                served_series=ys[1] if record else None,
                unmetered_g=(acc[-1] / 1000.0 * dt / 3600.0
                             if power_gap is not None else None),
            )


# ---------------------------------------------------------------------------
# Population sweep on the JAX path (backend="jax" in sweep_population)
# ---------------------------------------------------------------------------

@obs.sweep()
def sweep_population_jax(policies: dict, family: SliceFamily, traces,
                         carbon, targets: Sequence[float],
                         cfg_base: SimConfig,
                         demand_scale: float = 1.0,
                         placement=None, traffic=None,
                         elasticity=None, energy=None,
                         admission_impl: str = "auto",
                         faults=None) -> list:
    """JAX-backed `sweep_population`: one device-resident scan per policy
    over all (target x trace) columns, same aggregate rows, same order,
    as the fleet backend (parity pinned <= 1e-6 by the test suite).

    With `placement`, the shared region plan is computed by the JAX
    placement kernel (`repro.cluster.placement_jax.plan_jax`) on the
    real n_tr-column fleet, exactly as the fleet backend does with the
    NumPy planner — and the sweep takes the memory-lean path: compact
    (T, n_tr) demand plus the plan's (region_intensity, assign-codes)
    indexed carbon, tiled to the logical n_tr*n_tg fleet *inside* the
    scan step, so no (T, N) matrix is ever materialized (the fleet
    backend's tiled form is ~2.3 GB per matrix at N=1M). The indexed
    select reproduces the gathered matrix bit-exactly, so sweep parity
    with the fleet backend is unchanged. The codes are the plan's device
    assignments, and the demand its device copy where the scan runs on
    the very demand the plan was given: neither goes through the host. `admission_impl` is forwarded
    to `plan_jax` ("auto" | "xla" | "pallas").

    With `faults` (a `repro.robustness.FaultPlan`), the observed/true
    split is materialized host-side by the *shared* prologue — the jax
    planner threads the same seeded migration-failure mask, the scan
    decides on the (T, R) observed region matrix (R-way selected in
    step, so still nothing (T, N)) while billing the true one, and
    power-telemetry gaps accrue `unmetered_g` — so the degraded
    signals are identical to the fleet backend's by construction.
    """
    def _plan(eng, demand_plan, flt):
        from repro.cluster.placement_jax import plan_jax
        return plan_jax(eng, demand_plan, state_gb=cfg_base.state_gb,
                        admission_impl=admission_impl, faults=flt)

    compact = placement is not None
    with obs.span("sweep.prepare"):
        (demand_one, tgt_one, carbon, plan, n_tr, n_tg, grid_up,
         fault_ctx) = _prepare_sweep_inputs(
            traces, carbon, targets, cfg_base, demand_scale, placement,
            _plan, tile=not compact, energy=energy, faults=faults)
    n_rep = 1
    carbon_obs = None
    gap_vec = fault_ctx.gap_vec if fault_ctx is not None else None
    if compact:
        # the scan reads the plan's codes where the planner left them
        codes = (plan.assign if plan.assign_device is None
                 else plan.assign_device)
        if fault_ctx is None:
            carbon = (plan.region_intensity, codes)
        else:
            # bill at the TRUE region intensities; the plan's own table
            # (region_intensity) IS the observed feed under faults and
            # becomes the scan's decision signal
            carbon = (fault_ctx.true_reg, codes)
            carbon_obs = plan.region_intensity
        n_rep = n_tg
    elif fault_ctx is not None:
        obs_reg = fault_ctx.obs_reg
        carbon_obs = (np.tile(obs_reg, (1, n_tg)) if obs_reg.ndim == 2
                      else obs_reg)

    traffic_summary = None
    run_traffic = None
    T = demand_one.shape[0]
    # compact host pipeline, pinned layer order (see the fleet backend):
    # demand_scale -> traffic -> energy -> elasticity
    comp = None
    if energy is not None or elasticity is not None:
        comp = demand_one                       # compact (T, n_tr)
        if demand_scale is not None and np.any(
                np.asarray(demand_scale) != 1.0):
            comp = comp * demand_scale
    if traffic is not None:
        from repro.traffic.sim_jax import TrafficSpec
        with obs.span("sweep.traffic"):
            arr, tres = _prepare_traffic(traffic, plan, T,
                                         cfg_base.interval_s)
            traffic_summary = tres.summary()
            if elasticity is None:
                # the in-scan traffic_step fold drives the demand modulation
                # on device; the serving-ledger row metrics come from the
                # (tiny, (T, R)) NumPy pipeline — parity between the two is
                # pinned <=1e-6 by the jax traffic tests
                run_traffic = (TrafficSpec.from_config(traffic,
                                                       cfg_base.interval_s),
                               arr.requests)
            if comp is not None:
                # the host-side compact pipeline (energy supply load,
                # elasticity forecasters) needs the modulation as host
                # floats — same gather as the fleet backend (with
                # elasticity this also keeps the level counts exact, not
                # just 1e-6-close)
                mod = tres.demand_mod(traffic.demand_gain)
                comp = comp * mod[np.arange(T)[:, None], plan.assign[:T]]

    energy_summary = None
    run_energy = None
    ela_forecast = None
    if fault_ctx is not None and compact:
        # controller-side forecast feed: the observed grid (overridden
        # below onto the delivered mix when the energy layer is on)
        ela_forecast = plan.region_intensity
    if energy is not None:
        with obs.span("sweep.energy"):
            spec_e, sres, solar_mat, cap_cols, ceff_cols = _prepare_energy(
                energy, family, plan, comp, T, cfg_base.interval_s,
                grid_up, region_mat=(fault_ctx.true_reg
                                     if fault_ctx is not None else None))
            energy_summary = sres.summary()
            if elasticity is None:
                # in-scan fold: the scan re-derives the supply ledger on
                # device from the (traffic-modulated) demand and applies
                # cap/c_eff per epoch; the energy_* row metrics above come
                # from the shared host simulation (the two agree <=1e-6,
                # pinned by the energy tests). Under faults the raw
                # observed grid rides along as obs_mat and the step scales
                # it onto the delivered mix by the observed/true ratio.
                run_energy = (spec_e, solar_mat, grid_up)
            else:
                # with elasticity downstream the cap must land *before* the
                # demand forecasters — host-applied, same floats as the
                # fleet backend; billing (and the carbon forecast) switch
                # to the delivered mix's effective intensity
                comp = comp * cap_cols
                carbon = (sres.c_eff, codes)
                if fault_ctx is not None:
                    # observed delivered mix: true effective intensity
                    # scaled by the per-region observed/true grid ratio —
                    # same host floats as the fleet backend
                    tr = fault_ctx.true_reg[:T]
                    safe = np.where(tr > 0.0, tr, 1.0)
                    ratio = np.where(tr > 0.0,
                                     fault_ctx.obs_reg[:T] / safe, 1.0)
                    carbon_obs = sres.c_eff * ratio
                    ela_forecast = carbon_obs

    elastic_summary = None
    if elasticity is not None:
        if plan is None:
            raise ValueError("elasticity requires placement")
        from repro.core.elasticity_jax import simulate_elastic_jax
        # separate compact-width scan (NOT folded into the sharded fleet
        # scan — the (N·K,) argsort would run once per device shard);
        # its served demand is what the fleet below advances on. With
        # energy on, `carbon` is the (c_eff, codes) indexed pair, so
        # both the actual intensity and its forecast see the delivered
        # mix — exactly like the fleet backend's ceff_reg forecast.
        with obs.span("sweep.elastic_budget"):
            budget = _elastic_budget_series(plan, T, elasticity,
                                            cfg_base.interval_s)
        with obs.span("sweep.elastic"):
            # the elasticity scan gathers on the host: the plan's host
            # codes
            eres = simulate_elastic_jax(comp, (carbon[0], plan.assign),
                                        elasticity,
                                        cfg_base.interval_s,
                                        budget_series=budget,
                                        carbon_forecast=ela_forecast)
            demand_one = eres.demand_served()
            elastic_summary = eres.summary()
        demand_scale = 1.0          # already applied ahead of the layer

    # the planner's device demand stands in for a push where the run
    # would push the very array the plan was given: not after
    # elasticity, nor under a demand_scale (each makes a new array)
    demand_device = None
    if plan is not None and plan.demand_device is not None \
            and plan.demand_device[0] is demand_one:
        demand_device = plan.demand_device[1]
    sim = FleetSimulatorJax(
        family, interval_s=cfg_base.interval_s,
        suspend_releases_slice=cfg_base.suspend_releases_slice)
    results = {}
    for name, mk_policy in policies.items():
        results[name] = (sim.run(mk_policy(), demand_one, carbon, tgt_one,
                                 epsilon=cfg_base.epsilon,
                                 state_gb=cfg_base.state_gb,
                                 demand_scale=demand_scale,
                                 n_rep=n_rep, traffic=run_traffic,
                                 energy=run_energy,
                                 carbon_obs=carbon_obs,
                                 power_gap=gap_vec,
                                 demand_device=demand_device), 0)
    fault_summary = None
    if fault_ctx is not None:
        fault_summary = fault_ctx.signal.summary()
        if plan is not None and plan.failed_migrations is not None:
            fault_summary["fault_failed_migrations_mean"] = float(
                np.mean(plan.failed_migrations))
    with obs.span("sweep.aggregate"):
        return _aggregate_sweep_rows(policies, results, targets, n_tr, plan,
                                     traffic_summary, elastic_summary,
                                     energy_summary, fault_summary)
