#!/usr/bin/env python3
"""Smoke run of the fleet sweep on a TPU: one process, start to finish.

It prints the device JAX sees and stops with a non-zero exit unless the
platform is ``tpu``. Then:

1. it runs the N = 1,000,000 sweep of ``make jax-sweep``
   (`benchmarks.figs.fleet_1m_spec`: 100,000 Azure-like traces x 10
   targets, 288 five-minute epochs, R = 3 regions, placement at 60%
   capacity, a 1M-user traffic layer, K = 4 elasticity under a shaped
   budget, the energy supply with an outage and a shock, and the
   20%-dropout fault plan) twice through ``SweepSpec(backend="jax")``,
   and prints the set-up time (first call, compilation included) and
   the steady time (second call) of this one run — a smoke run, not a
   benchmark;
2. it checks the invariants ``make jax-sweep`` gates on: identical rows
   from both calls, no over-capacity epoch, energy conservation within
   1e-6 W, no cap, SoC or elastic-cap violation, N = 1,000,000, and no
   compilation in the steady call;
3. it checks the chip against the host: the same spec at 5,000 traces x
   10 targets on the NumPy ``fleet`` backend, with counts (migrations,
   levels, violations, plan assignments) exact and floats within the
   repo's 1e-6 fleet<->jax anchor, and it counts the ``tpu_custom_call``
   ops in the compiled region plan (the Pallas admission kernel).

Any failed check exits non-zero before the last line, which is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # container axis split over 4 chips

``--four-chips`` runs the same phases with the fleet scan's container
axis split across the host's four chips.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

PARITY_TOL = 1e-6                    # the fleet<->jax anchor
COUNT_KEYS = ("migrations_mean", "placement_migrations_mean",
              "elastic_level_epochs", "elastic_cap_violations",
              "energy_cap_violations", "energy_soc_violations",
              "energy_outage_epochs", "fault_failed_migrations_mean")


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str):
    print(f"  [{'ok' if ok else 'FAILED'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Counts backend compilations, their seconds, and persistent-cache
    hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._hit)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _hit(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


def timed_run(spec, clock, label):
    c0, s0, h0 = clock.snapshot()
    t0 = time.perf_counter()
    res = spec.run()
    wall = time.perf_counter() - t0
    c1, s1, h1 = clock.snapshot()
    print(f"  {label}: wall_s={wall!r} compiles={c1 - c0} "
          f"compile_s={s1 - s0!r} cache_hits={h1 - h0}", flush=True)
    return res, wall, c1 - c0


def fleet_shards(n_devices, n_traces, n_targets):
    """How many devices the jax fleet scan splits the placed sweep over
    (its targets are the rep blocks)."""
    from repro.core.fleet_jax import shard_count
    return shard_count(n_devices, n_traces * n_targets, n_targets)


def kernel_calls(view, spec):
    """`tpu_custom_call` ops in the compiled region plan of `spec`."""
    from repro.cluster.placement_jax import lower_plan
    text = lower_plan(view, spec.traces, state_gb=spec.sim.state_gb,
                      faults=spec.faults).compile().as_text()
    return text.count("tpu_custom_call")


def sweep_phase(clock, n_devices, n_traces=100_000, n_targets=10):
    """The N=1M sweep twice, its invariants and the plan's kernel."""
    from benchmarks.figs import fleet_1m_spec, planner_view
    from repro.cluster.placement_jax import plan_jax

    N = n_traces * n_targets
    print(f"phase 1: jax sweep, N={N} ({n_traces} traces x {n_targets} "
          f"targets), all layers + fault plan, split over "
          f"{fleet_shards(n_devices, n_traces, n_targets)} device(s) "
          f"[one smoke run, not a benchmark]", flush=True)
    t0 = time.perf_counter()
    spec = fleet_1m_spec(n_traces, n_targets)
    print(f"  inputs: gen_s={time.perf_counter() - t0!r} "
          f"T={spec.traces.shape[0]}", flush=True)
    first, setup_s, _ = timed_run(spec, clock, "set-up call (first)")
    second, steady_s, steady_compiles = timed_run(spec, clock,
                                                  "steady call (second)")
    T = spec.traces.shape[0]
    print(f"  setup_s={setup_s!r} steady_s={steady_s!r} "
          f"container_epochs_per_s={N * T / steady_s!r}", flush=True)

    view = planner_view(spec)
    plan = plan_jax(view, spec.traces, state_gb=spec.sim.state_gb,
                    faults=spec.faults)
    cap = spec.placement.config.capacity
    over = int((plan.occupancy() > cap).sum())
    rows = second.rows
    conservation = max(r["energy_conservation_max_err_w"] for r in rows)
    print(f"  placement_migrations={int(plan.migrations.sum())} "
          f"over_capacity_epochs={over} "
          f"energy_conservation_max_err_w={conservation!r}", flush=True)
    check(spec.traces.shape[1] * len(spec.targets) == N,
          f"n_containers == {N}")
    check(first.rows == second.rows, "rows identical across the two calls")
    check(steady_compiles == 0, "no compilation in the steady call")
    check(over == 0, "over_capacity_epochs == 0")
    check(conservation <= 1e-6, "energy conservation <= 1e-6 W")
    for key in ("energy_cap_violations", "energy_soc_violations",
                "elastic_cap_violations"):
        worst = max(r[key] for r in rows)
        check(worst == 0, f"{key} == 0 (got {worst})")
    n_kernel = kernel_calls(view, spec)
    print(f"  tpu_custom_calls_in_plan={n_kernel}", flush=True)
    check(n_kernel > 0, "the compiled plan holds the Pallas admission "
                        "kernel")


def parity_phase(clock, n_devices, n_traces=5000, n_targets=10):
    """Chip vs host (`backend="fleet"`) on the same spec at reduced N."""
    from benchmarks.figs import fleet_1m_spec, planner_view
    from repro.cluster.placement_jax import plan_jax

    print(f"phase 2: chip vs host at N={n_traces * n_targets} "
          f"({n_traces} traces x {n_targets} targets, split over "
          f"{fleet_shards(n_devices, n_traces, n_targets)} device(s))",
          flush=True)
    spec = fleet_1m_spec(n_traces, n_targets)
    chip, _, _ = timed_run(spec, clock, "chip (jax)")
    host, _, _ = timed_run(dataclasses.replace(spec, backend="fleet"),
                           clock, "host (fleet)")
    for key in COUNT_KEYS:
        a, b = chip.col(key), host.col(key)
        check(bool((a == b).all()), f"{key} exact (chip {a.tolist()[:3]}.."
                                    f" host {b.tolist()[:3]}..)")
    drift = chip.parity(host)
    tos = max(abs(a["time_on_slice"].get(k, 0.0)
                  - b["time_on_slice"].get(k, 0.0))
              for a, b in zip(chip.rows, host.rows)
              for k in set(a["time_on_slice"]) | set(b["time_on_slice"]))
    print(f"  sweep_parity_max_rel_diff={drift!r} "
          f"time_on_slice_max_abs_diff={tos!r}", flush=True)
    check(drift <= PARITY_TOL, f"row floats within {PARITY_TOL} (relative)")
    check(tos <= PARITY_TOL, f"time-on-slice within {PARITY_TOL}")

    view = planner_view(spec)
    kw = dict(state_gb=spec.sim.state_gb, faults=spec.faults)
    pj = plan_jax(view, spec.traces, **kw)
    pn = view.plan(spec.traces, **kw)
    overhead = float(abs(pj.overhead_g - pn.overhead_g).max())
    print(f"  plan: migrations={int(pj.migrations.sum())} "
          f"failed={int(pj.failed_migrations.sum())} "
          f"overhead_max_abs_diff={overhead!r}", flush=True)
    check(bool((pj.assign == pn.assign).all()), "plan assignments exact")
    check(bool((pj.migrations == pn.migrations).all())
          and bool((pj.failed_migrations == pn.failed_migrations).all()),
          "plan migration counts exact")
    check(overhead <= PARITY_TOL, f"plan overhead within {PARITY_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="split the fleet scan over the host's 4 chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform!r}; this smoke run needs "
              f"the chip", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) != want:
        print(f"expected {want} chip(s), JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        sweep_phase(clock, len(devices))
        parity_phase(clock, len(devices))
    except SmokeFailure as e:
        print(f"smoke run failed: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0!r} s "
          f"(compiles={clock.compiles} compile_s={clock.compile_s!r} "
          f"cache_hits={clock.cache_hits})", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
