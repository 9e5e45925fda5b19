"""Run one cell of the chip benchmark once, in this process.

    python3 -m bench.run --workload r3_placed --seed 7 --seconds 10 --trace 0

The run prints the device JAX sees and stops with exit code 2, printing no
result, unless that is a TPU with as many chips as the cell asks for. Then:

1. set-up: find the cell's reference through its traffic mix and refuse
   a mix that turns on a layer the reference does not model; make the
   cell's inputs from the seed (`bench.gen`, with each layer's own), build
   the sweep (`SweepSpec(backend="jax")` with each layer's settings,
   `bench.sweep`) and run it once, so that every program the window
   drives is compiled or read from JAX's persistent cache (kept in the
   checkout by `repro.compile_cache`);
2. window: run whole sweeps back to back until `--seconds` have passed; a
   sweep cannot be split, so the window ends on the first sweep boundary at
   or after `--seconds`. With `--trace 1` the window is one sweep under the
   profiler, and the per-layer metrics are read from its trace. Each
   sweep's duration is printed on standard error;
3. check: a sample of the rows, drawn from the seed, and the region plan
   where the reference makes one, are recomputed by the mix's reference
   (`bench/ref/<reference>.py`) and compared (`bench.check`).

A traced run fails, printing no result, when a per-layer metric that
`BENCHMARK.json` lists for the cell reads nothing.

The last line of standard output is one JSON object: `correct`, `attempted`
(sweeps in the window), `failed` (sweeps whose rows differ from the
warm-up sweep's), `metrics`, `device`, with `--trace 1` a `breakdown`, and
last `checks`, each number compared beside its limit. The same numbers end
standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

from bench import cells  # noqa: E402

SPAN = "bench.sweep"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_error(devices, chips: int):
    """Why this process cannot run a cell on `chips` chips, or None."""
    d0 = devices[0]
    if d0.platform != "tpu":
        return f"no TPU: JAX found {d0.platform!r}; the benchmark needs the chip"
    if len(devices) < chips:
        return f"the cell needs {chips} chip(s), JAX found {len(devices)}"
    return None


def _sweep(spec):
    return spec.run().rows


def _lapped(step, laps: list):
    """`step`, appending the seconds each call takes to `laps`."""
    def lapped():
        t = time.perf_counter()
        out = step()
        laps.append(time.perf_counter() - t)
        return out
    return lapped


def window(step, seconds: float, clock=time.perf_counter):
    """Call `step` back to back until `seconds` have passed; a call is never
    cut, so the window ends on the first call boundary at or after
    `seconds`. Returns the results and the window's length."""
    out = []
    t0 = clock()
    while True:
        out.append(step())
        elapsed = clock() - t0
        if elapsed >= seconds:
            return out, elapsed


def layer_metrics(bench: dict, name: str, ctx) -> dict:
    """Cell `name`'s per-layer metrics, each read by its reader; one that
    reads nothing fails the run, since `BENCHMARK.json` lists it here."""
    metrics = {}
    for m in cells.per_layer(bench, name):
        v = cells.reader(m["name"]).read(ctx)
        if v is None:
            raise RuntimeError(
                f"metric {m['name']!r}, listed for cell {name!r}, read "
                f"nothing: its program or kernel is not in the trace")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, t_start: float,
             sizes: dict = None) -> dict:
    """One run of cell `name`; returns the result object. `sizes` overrides
    configuration sizes (the tests run cells at a few traces on the CPU)."""
    import jax

    from bench import check, sweep
    from bench import trace as tr
    from bench.clock import CompileClock
    from bench.gen.fleet import make_inputs
    from bench.ref import placed

    cell = cells.workload(bench, name)
    cfg = {**cells.config(bench, cell["config"]), **(sizes or {})}
    mix = cells.mix(cell["traffic"])
    ref = cells.reference(mix)
    used = devices[:cell["chips"]]
    clock = CompileClock()

    t = time.perf_counter()
    inputs = make_inputs(cfg, seed, mix)
    gen_s = time.perf_counter() - t
    spec = sweep.program_sweep(cfg, mix, inputs)
    with sweep.PlanTap() as tap:
        warm = _sweep(spec)
        warm_plan = tap.last
        setup_s = time.perf_counter() - t_start
        compiles0, compile_s, hits = clock.snapshot()
        print(f"set-up: setup_s={setup_s!r} gen_s={gen_s!r} "
              f"compiles={compiles0} compile_s={compile_s!r} "
              f"cache_hits={hits}", file=sys.stderr, flush=True)

        traced = None
        laps = []
        step = _lapped(lambda: _sweep(spec), laps)
        if trace:
            with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
                jax.profiler.start_trace(tdir)
                with jax.profiler.TraceAnnotation(SPAN):
                    sweeps, window_s = window(step, 0.0)
                jax.profiler.stop_trace()
                traced = tr.from_xplane(sorted(glob.glob(
                    f"{tdir}/plugins/profile/*/*.xplane.pb"))[-1], SPAN)
        else:
            sweeps, window_s = window(step, seconds)
        last_plan = tap.last
    window_compiles = clock.snapshot()[0] - compiles0
    stats = [d.memory_stats() for d in used]
    mem = max((s or {}).get("peak_bytes_in_use", 0) for s in stats)
    print(f"window: sweeps={len(sweeps)} window_s={window_s!r} "
          f"compiles={window_compiles}", file=sys.stderr, flush=True)
    print(f"window: sweep_s={laps!r}", file=sys.stderr, flush=True)

    T, n_tr = inputs["traces"].shape
    n = n_tr * len(inputs["targets"])
    del spec
    gc.collect()

    t = time.perf_counter()
    targets = check.sampled_targets(inputs["targets"], mix["check_targets"],
                                    seed)
    ref_rows, ref_plan = ref.sweep(cfg, inputs, targets)
    plans = None
    if ref_plan is not None:
        R, cap = len(cfg["regions"]), placed.capacity(cfg)
        got = [getattr(p, "assign", None) for p in (warm_plan, last_plan)]
        plans = {
            "plan_mismatches": sum(
                check.plan_mismatches(a, ref_plan["assign"]) for a in got),
            "over_capacity_epochs": sum(
                check.over_capacity_epochs(a, R, cap)
                for a in got if a is not None)}
    verdict = check.judge(warm, sweeps, ref_rows, cfg["limits"],
                          window_compiles, plans, ref.COUNT_KEYS)
    print(f"check: targets={targets} reference_s={time.perf_counter() - t!r} "
          f"worst_key={verdict['worst_key']}", file=sys.stderr, flush=True)

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": verdict["correct"], "attempted": len(sweeps),
           "failed": verdict["failed"]}
    if trace:
        ctx = SimpleNamespace(
            trace=traced, peaks=peaks,
            setup={"compile_s": compile_s, "gen_s": gen_s},
            dims={"n_traces": n_tr, "n_targets": len(inputs["targets"]),
                  "T": T, "R": len(cfg["regions"])})
        metrics = layer_metrics(bench, name, ctx)
        device.update(busy_s=tr.busy_s(traced), window_s=traced.window_s)
        out.update(metrics=metrics, device=device, breakdown={
            "device_ops": tr.top_ops(traced), "idle_gaps": tr.idle_gaps(traced)})
    else:
        units = {m["name"]: m["unit"] for m in cells.end_to_end(bench, name)}
        out.update(metrics={
            "container_epochs_per_s": {
                "value": len(sweeps) * n * T / window_s,
                "unit": units["container_epochs_per_s"]},
            "setup_s": {"value": setup_s, "unit": units["setup_s"]}},
            device=device)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in verdict["checks"].items()}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    bench = cells.load()
    cell = cells.workload(bench, args.workload)
    sys.path.insert(0, str(cells.ROOT / "src"))
    import jax

    from bench.peaks import peaks

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    err = device_error(devices, cell["chips"])
    if err:
        print(err, file=sys.stderr)
        return 2
    chip_peaks = peaks(d0.device_kind)

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    # cache every program, so that a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), devices, chip_peaks, T_START)
    for k, c in out["checks"].items():
        print(f"{k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
