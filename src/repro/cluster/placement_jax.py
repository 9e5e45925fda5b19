"""JAX port of the multi-region placement planner: jit/scan, device-resident.

`repro.cluster.placement.PlacementEngine.plan` advances an (N,) fleet's
region assignment epoch by epoch with NumPy array state — fast enough
for hundreds of containers, but the per-epoch Python round-trip caps
fleet-scale what-if sweeps. This module runs the same decision model as
one `jax.lax.scan` over epochs:

  - the (N, R) migrate/stay kernel (horizon-amortized saving vs
    stop-and-copy cost, hysteresis + min-dwell) evaluates per epoch on
    device, float64 end-to-end (`enable_x64`, scoped);
  - capacity admission runs the same preference rounds as the NumPy
    kernel inside a `lax.while_loop` bounded at R rounds, with the NumPy
    loop's early exit (a round that wants nothing or denies nothing ends
    the loop — further rounds would be no-ops); the round carry is two
    packed int32 vectors (dst + a denied-region strike bitmask) plus the
    (R,) free-slot counters, so no (N, R) tensor outlives a round; note
    the data-dependent trip count means the planner is not
    reverse-differentiable as-is — switch to a fixed-trip fori_loop
    first if you need gradients through admission;
  - one host->device push of (cmat, demand, cost0, mig_s), one pull of
    the final carry and the (T,) count of preference rounds each epoch
    ran. The (T, N) int32 assignment matrix and the pushed demand stay on
    the device, in the plan, for the fleet scan to take up
    (`repro.core.fleet_jax.sweep_population_jax`); the plan's host
    `assign` is pulled from them only when something reads it.

Why the ranked admission is the one hot path XLA handles badly
--------------------------------------------------------------
Admission is a *sequential contention loop*: container i wins region r
iff fewer than ``remaining[r]`` wanters of r precede it in index order.
The pure-XLA rendering (``admission_impl="xla"``) ranks wanters with a
global ``lax.associative_scan`` over the (N, R) one-hot request matrix —
an O(N R log N) multi-pass tree whose log N intermediate (N, R) stages
each round-trip through memory; on XLA:CPU (no multi-output loop
fusion, see `repro.core.fleet_jax`) the surrounding argmax/strike chain
is then re-materialized per stage, and a ``lax.cond`` fast path that
skips ranking when every request fits only helps uncontended epochs.
The Pallas kernel (``admission_impl="pallas"``,
`repro.cluster.placement_pallas`) instead streams container blocks
through a grid with per-region "wanters seen so far" counters in SMEM —
rank becomes counter + in-block prefix count, and the whole round is
one O(N R) pass with the argmax, ranking, admission, and strike fused
in a single kernel. It reads the epoch's net savings as int32
preference ranks (`placement_pallas.preference_ranks`, computed once
per epoch in f64), so its decisions equal the f64 argmax's. ``"auto"``
picks pallas on a TPU, where Mosaic compiles it, and the XLA rendering
everywhere else; off a TPU an explicit ``"pallas"`` runs in interpret
mode (correct and parity-tested, but built from the same XLA ops it is
meant to replace).

The result is the same `PlacementPlan` dataclass; parity against the
NumPy planner is pinned to 1e-6 (assignments equal epoch-by-epoch) by
`tests/test_placement_jax.py` for both admission impls (pallas in
interpret mode), and the NumPy planner stays pinned bit-compatible to
the greedy scalar reference, anchoring the chain.

Degenerate shapes short-circuit before tracing: an empty fleet (N=0), a
single region (R=1, where no container can ever move), or an empty
horizon (T=0) return the trivial plan without compiling the scan.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from repro import obs
from repro.cluster.placement import PlacementPlan

import jax
import jax.numpy as jnp
from jax import lax

ADMISSION_IMPLS = ("auto", "xla", "pallas")


def _sel_region(c_row, idx, R: int):
    """(N,) gather of the (R,) epoch intensities at per-container region
    indices, as a select chain (R is small and static)."""
    out = jnp.full(idx.shape, c_row[0], dtype=jnp.float64)
    for r in range(1, R):
        out = jnp.where(idx == r, c_row[r], out)
    return out


def _admission_round_xla(net, assign, eligible, dst, struck, remaining,
                         rows_r):
    """One preference round, pure-XLA: associative-scan ranking with a
    `lax.cond` fast path for uncontended rounds. Same (dst, struck,
    want_total) outputs as `placement_pallas.admission_round`, which
    reads the round-invariant `net` as integer preference ranks."""
    cols = rows_r[None, :]
    net_eff = jnp.where(((struck[:, None] >> cols) & 1) > 0, -jnp.inf, net)
    best = jnp.argmax(net_eff, axis=1).astype(jnp.int32)
    net_best = jnp.max(net_eff, axis=1)
    want = eligible & (dst < 0) & (net_best > 0.0) & (best != assign)
    onehot = want[:, None] & (best[:, None] == cols)
    counts = onehot.sum(axis=0, dtype=jnp.int32)

    def admit_all(_):
        return onehot

    def admit_ranked(_):
        rank = lax.associative_scan(jnp.add, onehot.astype(jnp.int32),
                                    axis=0)
        return onehot & (rank <= remaining[None, :])

    adm = lax.cond(jnp.all(counts <= remaining), admit_all, admit_ranked,
                   None)
    admitted = adm.any(axis=1)
    dst = jnp.where(admitted, best, dst)
    denied = want & ~admitted
    struck = jnp.where(denied, struck | (1 << best), struck)
    return dst, struck, counts


@partial(jax.jit,
         static_argnames=("R", "min_dwell", "has_cap", "base_b", "span_b",
                          "mult_b", "h_hr", "hk", "admission_impl",
                          "block_n", "interpret", "has_faults", "bb", "bc"))
def _plan_scan(cmat, demand, assign0, occ0, cap, cost0, mig_s,
               fail_mat=None, *, R: int,
               min_dwell: int, has_cap: bool, base_b: float, span_b: float,
               mult_b: float, h_hr: float, hk: float,
               admission_impl: str = "xla", block_n: int = 8192,
               interpret: bool = True, has_faults: bool = False,
               bb: int = 1, bc: int = 16):
    """One XLA computation for the whole planning horizon. Mirrors
    `PlacementEngine.plan` term-for-term (see its docstring for the
    decision model). `admission_impl` here is already resolved to
    "xla" or "pallas" (`plan_jax` resolves "auto"). With
    `has_faults`, `fail_mat` is the shared (T, N) failed-migration
    mask and the carry gains the retry state (fail streak + earliest
    retry epoch, capped exponential backoff `min(bb * 2**k, bc)`).

    Returns the final carry, the (T, N) int32 assignments and, with
    `has_cap`, the (T,) int32 preference rounds each epoch ran (else
    None)."""
    N = demand.shape[1]
    rows_r = jnp.arange(R, dtype=jnp.int32)
    T = demand.shape[0]
    t_vec = jnp.arange(T, dtype=jnp.int64)

    def step(st, x):
        if has_faults:
            (assign, dwell, migrations, overhead_g, downtime_s, occ,
             fail_cnt, retry_at, failed_migrations) = st
            c_row, d, fail_row, t_i = x
        else:
            assign, dwell, migrations, overhead_g, downtime_s, occ = st
            c_row, d = x
        p_est = base_b + span_b * jnp.minimum(d / mult_b, 1.0)
        c_cur = _sel_region(c_row, assign, R)
        save = (p_est[:, None] * (c_cur[:, None] - c_row[None, :])
                / 1000.0 * h_hr)
        cost = (cost0[:, None] * (0.5 * (c_cur[:, None] + c_row[None, :]))
                / 1000.0)
        net = save - hk * cost                     # (N, R)
        eligible = dwell >= min_dwell
        if has_faults:
            eligible = eligible & (t_i >= retry_at)

        if not has_cap:
            best = jnp.argmax(net, axis=1).astype(jnp.int32)
            net_best = jnp.max(net, axis=1)
            m = eligible & (net_best > 0.0) & (best != assign)
            dst = jnp.where(m, best, -1)
        else:
            # preference rounds, bounded at R like the NumPy kernel and
            # with its early exit (a round with nothing wanted or
            # nothing denied ends the loop — extra rounds would be
            # no-ops). The round carry is packed int32 (dst + strike
            # bitmask); `net` stays round-invariant and denied choices
            # accumulate in the bitmask, so admitted(r) ==
            # min(want_total[r], remaining[r]) closes the counters.
            remaining0 = cap - occ
            if admission_impl == "pallas":
                from repro.cluster.placement_pallas import (
                    admission_round, preference_ranks)
                pref = preference_ranks(net)         # round-invariant

            def round_cond(rst):
                _, _, _, rnd, cont = rst
                return cont & (rnd < R)

            def round_body(rst):
                dst_r, struck_r, remaining_r, rnd, _ = rst
                if admission_impl == "pallas":
                    dst_r, struck_r, want_tot = admission_round(
                        pref, assign, eligible, dst_r, struck_r,
                        remaining_r, block_n=block_n, interpret=interpret)
                else:
                    dst_r, struck_r, want_tot = _admission_round_xla(
                        net, assign, eligible, dst_r, struck_r,
                        remaining_r, rows_r)
                admitted_tot = jnp.minimum(want_tot, remaining_r)
                remaining_n = remaining_r - admitted_tot
                cont = (jnp.any(want_tot > 0)
                        & jnp.any(want_tot > admitted_tot))
                return (dst_r, struck_r, remaining_n, rnd + 1, cont)

            dst0 = jnp.full(N, -1, dtype=jnp.int32)
            struck0 = jnp.zeros(N, dtype=jnp.int32)
            dst, _, remaining, rounds, _ = lax.while_loop(
                round_cond, round_body,
                (dst0, struck0, remaining0, jnp.int32(0), jnp.bool_(True)))

        attempted = dst >= 0
        if has_faults:
            failed = attempted & fail_row
            moved = attempted & ~failed
        else:
            moved = attempted
        dst_c = jnp.where(attempted, dst, 0)
        c_dst = _sel_region(c_row, dst_c, R)
        # every attempt — failed or not — pays stop-and-copy: the
        # container was checkpointed and (partially) copied before the
        # destination rejected it
        overhead_g = overhead_g + jnp.where(
            attempted, cost0 * (0.5 * (c_cur + c_dst)) / 1000.0, 0.0)
        downtime_s = downtime_s + jnp.where(attempted, mig_s, 0.0)
        migrations = migrations + moved
        if has_faults:
            failed_migrations = failed_migrations + failed
            fail_cnt = jnp.where(failed, fail_cnt + 1,
                                 jnp.where(moved, 0, fail_cnt))
            k = jnp.minimum(fail_cnt - 1, 20)
            delay = jnp.minimum(bb * (2 ** jnp.maximum(k, 0)), bc)
            retry_at = jnp.where(failed, t_i + 1 + delay, retry_at)
        if has_cap:
            src_oh = moved[:, None] & (assign[:, None] == rows_r[None, :])
            dst_oh = moved[:, None] & (dst_c[:, None] == rows_r[None, :])
            occ = (occ - src_oh.sum(axis=0, dtype=jnp.int32)
                   + dst_oh.sum(axis=0, dtype=jnp.int32))
        assign = jnp.where(moved, dst, assign)
        dwell = jnp.where(moved, 0, dwell + 1)
        ys = (assign, rounds if has_cap else None)
        if has_faults:
            return (assign, dwell, migrations, overhead_g, downtime_s,
                    occ, fail_cnt, retry_at, failed_migrations), ys
        return (assign, dwell, migrations, overhead_g, downtime_s,
                occ), ys

    N_ = demand.shape[1]
    carry0 = (assign0,
              jnp.full(N_, 10 ** 6, dtype=jnp.int32),    # first move free
              jnp.zeros(N_, dtype=jnp.int32),
              jnp.zeros(N_, dtype=jnp.float64),
              jnp.zeros(N_, dtype=jnp.float64),
              occ0)
    if has_faults:
        carry0 = carry0 + (jnp.zeros(N_, dtype=jnp.int64),   # fail_cnt
                           jnp.zeros(N_, dtype=jnp.int64),   # retry_at
                           jnp.zeros(N_, dtype=jnp.int64))   # failed count
        xs = (cmat, demand, fail_mat, t_vec)
    else:
        xs = (cmat, demand)
    carry, (assign_mat, rounds) = lax.scan(step, carry0, xs)
    return carry, assign_mat, rounds


def _trivial_plan(engine, cmat, assign0, has_faults=False) -> PlacementPlan:
    """Plan for shapes where no move is ever possible (N=0, R=1, T=0):
    every epoch keeps the initial assignment, zero overhead."""
    T = cmat.shape[0]
    N = assign0.shape[0]
    return PlacementPlan(
        assign=np.broadcast_to(assign0, (T, N)).copy(),
        migrations=np.zeros(N, dtype=np.int64),
        overhead_g=np.zeros(N, dtype=np.float64),
        downtime_s=np.zeros(N, dtype=np.float64),
        region_intensity=cmat,
        region_names=engine.region_names,
        initial=assign0.copy(),
        failed_migrations=np.zeros(N, dtype=np.int64) if has_faults
        else None)


def _prepare(engine, demand, state_gb, initial, admission_impl, block_n,
             faults):
    """Host inputs and static keywords of `_plan_scan` for one plan, or
    ``(None, prep)`` for a shape where nothing can ever move."""
    if admission_impl not in ADMISSION_IMPLS:
        raise ValueError(f"admission_impl must be one of {ADMISSION_IMPLS}, "
                         f"got {admission_impl!r}")
    from repro.cluster.placement_pallas import default_interpret
    from repro.robustness.faults import migration_failure_mask
    prep = engine._prep(demand, state_gb, initial)
    demand, cmat, cap, assign0, mig_s, cost0 = prep
    T, N = demand.shape
    R = engine.n_regions
    fail_mat = migration_failure_mask(faults, T, N)
    if N == 0 or R == 1 or T == 0:
        # nothing can ever move: N=0 has no containers, R=1 has no
        # destination (argmax == current region always), T=0 no epochs —
        # skip tracing/compiling the round loop entirely
        return None, (cmat, assign0, fail_mat is not None)
    if admission_impl == "auto":
        admission_impl = "xla" if default_interpret() else "pallas"
    t = engine.tables
    b = t.baseline_idx
    base_b = float(t.base_w[b])
    cfg = engine.config
    has_cap = cap is not None
    occ_host = (np.bincount(assign0, minlength=R).astype(np.int32)
                if has_cap else np.zeros(R, dtype=np.int32))
    cap_host = (cap.astype(np.int32) if has_cap
                else np.zeros(R, dtype=np.int32))
    kw = dict(R=R, min_dwell=int(cfg.min_dwell), has_cap=has_cap,
              base_b=base_b, span_b=float(t.peak_w[b]) - base_b,
              mult_b=float(t.multiple[b]),
              h_hr=float(cfg.horizon_intervals * engine.interval_s / 3600.0),
              hk=float(1.0 + cfg.hysteresis),
              admission_impl=admission_impl, block_n=int(block_n),
              interpret=default_interpret())
    if fail_mat is not None:
        kw.update(has_faults=True,
                  bb=int(faults.migration.backoff_base),
                  bc=int(faults.migration.backoff_cap))
    args = (cmat, demand, assign0.astype(np.int32), occ_host, cap_host,
            cost0, mig_s, fail_mat)
    return (args, kw), (cmat, assign0, fail_mat is not None)


def plan_jax(engine, demand, state_gb: float = 1.0, initial=None,
             admission_impl: str = "auto",
             block_n: int = 8192, faults=None) -> PlacementPlan:
    """Device-resident counterpart of `PlacementEngine.plan`: same
    inputs, same `PlacementPlan` out, one jit-compiled scan per shape.

    `admission_impl` selects the capacity-admission kernel: `"xla"`
    (associative-scan ranking), `"pallas"` (streaming Pallas kernel,
    compiled on a TPU and run in interpret mode elsewhere; `block_n`
    containers per grid step), or `"auto"` — pallas on a TPU, xla
    everywhere else (see module docstring). Both are pinned to the
    NumPy planner by the parity suite (and the planner to the scalar
    reference at 1e-9).

    `faults` (a `repro.robustness.FaultPlan`) injects the same seeded
    migration-failure mask as `PlacementEngine.plan` — failed attempts
    pay stop-and-copy but stay put and retry under capped exponential
    backoff; parity with the NumPy planner is preserved because the
    mask derivation is shared.

    It pulls the final carry and the (T,) admission rounds only. The
    (T, N) int32 assignments stay on the device as `assign_device`, and
    the pushed demand as `demand_device`, beside the host array it was
    pushed from; `assign` is pulled from `assign_device` on first read.
    """
    with obs.span("plan"):
        with obs.span("plan.prepare"):
            call, (cmat, assign0, has_faults) = _prepare(
                engine, demand, state_gb, initial, admission_impl, block_n,
                faults)
        if call is None:
            return _trivial_plan(engine, cmat, assign0,
                                 has_faults=has_faults)
        host_args, kw = call
        with jax.enable_x64(True):
            with obs.span("plan.h2d"):
                args = jax.block_until_ready(jax.device_put(host_args))
                obs.count("h2d_bytes", obs.nbytes(args))
            with obs.span("plan.wait"):
                carry, assign_mat, rounds = jax.block_until_ready(
                    _plan_scan(*args, **kw))
            with obs.span("plan.d2h"):
                # the (T, N) assignments stay on the device
                carry, rounds = jax.device_get((carry, rounds))
                obs.count("d2h_bytes", obs.nbytes((carry, rounds)))
                if rounds is not None:
                    obs.count("admission_rounds", rounds.sum())
                return PlacementPlan(
                    assign=None,
                    migrations=carry[2].astype(np.int64),
                    overhead_g=carry[3],
                    downtime_s=carry[4],
                    region_intensity=cmat,
                    region_names=engine.region_names,
                    initial=assign0.copy(),
                    failed_migrations=(carry[8].astype(np.int64)
                                       if has_faults else None),
                    admission_rounds=rounds,
                    assign_device=assign_mat,
                    demand_device=(host_args[1], args[1]))


def lower_plan(engine, demand, state_gb: float = 1.0, initial=None,
               admission_impl: str = "auto", block_n: int = 8192,
               faults=None):
    """The `jax.stages.Lowered` computation `plan_jax` runs for these
    inputs, for inspection (e.g. that a TPU plan holds the compiled
    admission kernel, a ``tpu_custom_call``)."""
    call, _ = _prepare(engine, demand, state_gb, initial, admission_impl,
                       block_n, faults)
    if call is None:
        raise ValueError("this plan is trivial: nothing is compiled")
    args, kw = call
    with jax.enable_x64(True):
        return _plan_scan.lower(*args, **kw)
