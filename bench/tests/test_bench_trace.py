"""The reduction from a trace to metrics: program times, the busy union,
idle gaps and their host labels, and the readers built on them."""
from types import SimpleNamespace

import pytest

from bench import cells
from bench import trace as tr

MS = 1_000_000
KERNEL = '%body.6 = (s32[8,128]) custom-call(...), custom_call_target="tpu_custom_call"'


def _synthetic():
    # chip 0: plan 0-10 ms, two kernel rounds inside it, idle 10-30 ms while
    # the host prepares, fleet scan 30-80 ms, idle 80-100 ms
    return tr.Trace(
        window=(0, 100 * MS),
        modules=[("jit__plan_scan(1)", 0, 10 * MS, 0),
                 ("jit__fleet_scan(2)", 30 * MS, 50 * MS, 0)],
        ops=[("fusion.1", 0, 4 * MS, 0),
             (KERNEL, 4 * MS, 2 * MS, 0),
             (KERNEL, 6 * MS, 2 * MS, 0),
             ("fusion.1", 8 * MS, 2 * MS, 0),
             ("while.3", 30 * MS, 50 * MS, 0),
             ("copy.2", 40 * MS, 5 * MS, 0)],          # overlaps while.3
        host=[("$run.py:1 run_cell", 0, 100 * MS),
              ("$fleet.py:830 _prepare_energy", 11 * MS, 18 * MS),
              ("$fleet.py:1096 _aggregate_sweep_rows", 81 * MS, 19 * MS)])


def test_merged_busy_and_gaps():
    t = _synthetic()
    assert tr.merged([(5, 8), (0, 3), (2, 4), (8, 9)]) == [[0, 4], [5, 9]]
    assert tr.busy_s(t) == pytest.approx(0.060)
    assert tr.gaps(t) == [(10 * MS, 30 * MS), (80 * MS, 100 * MS)]


def test_idle_gaps_are_named_by_the_innermost_covering_host_event():
    gaps = tr.idle_gaps(_synthetic())
    assert [g[0] for g in gaps] == ["$fleet.py:830 _prepare_energy",
                                    "$fleet.py:1096 _aggregate_sweep_rows"]
    assert [g[1] for g in gaps] == pytest.approx([0.020, 0.020])


def test_top_ops_and_program_times():
    t = _synthetic()
    # while.3 holds copy.2: ranked by self time, named by program
    top = tr.top_ops(t)
    assert top[0] == ["jit__fleet_scan/while.3", pytest.approx(0.045)]
    assert ["jit__plan_scan/fusion.1", pytest.approx(0.006)] in top
    assert tr.total_s(tr.matching(t.modules, "_plan_scan")) == 0.010
    assert tr.matching(t.modules, "scan_fn") == []


def test_clip_keeps_only_the_window():
    ev = [("a", -5, 10, 0), ("b", 95, 10, 0), ("c", 200, 5, 0)]
    assert tr._clip(ev, 0, 100) == [("a", 0, 5, 0), ("b", 95, 5, 0)]


def test_json_round_trip():
    t = _synthetic()
    u = tr.Trace.from_json(t.to_json())
    assert (u.window, u.modules, u.ops, u.host) == (t.window, t.modules,
                                                    t.ops, t.host)


def _ctx(t):
    return SimpleNamespace(trace=t, peaks={"hbm_bytes_per_s": 819e9},
                           setup={"compile_s": 1.5, "gen_s": 2.5},
                           dims={"n_traces": 100_000, "n_targets": 10,
                                 "T": 288, "R": 3})


def test_readers_on_the_synthetic_trace():
    ctx = _ctx(_synthetic())
    read = {n: cells.reader(n).read(ctx) for n in
            ("plan_ms", "fleet_scan_ms", "device_idle_pct",
             "admission_roofline_pct", "compile_s", "gen_s")}
    assert read["plan_ms"] == pytest.approx(10.0)
    assert read["fleet_scan_ms"] == pytest.approx(50.0)
    assert read["device_idle_pct"] == pytest.approx(40.0)
    assert (read["compile_s"], read["gen_s"]) == (1.5, 2.5)
    # two rounds of 4 * 100k * (3 + 6) bytes in 4 ms at 819 GB/s
    need = 2 * 3.6e6 / 819e9
    assert read["admission_roofline_pct"] == pytest.approx(100 * need / 0.004)


def test_admission_bytes_count_the_real_round():
    rb = cells.reader("admission_roofline_pct").round_bytes
    assert rb(100_000, 3) == 3_600_000
    assert rb(100_000, 27) == 4 * 100_000 * 33


def test_roofline_is_silent_without_kernel_events():
    t = _synthetic()
    t.ops = [e for e in t.ops if e[0] != KERNEL]
    assert cells.reader("admission_roofline_pct").read(_ctx(t)) is None


def _recorded():
    # the first 219 ms of a traced r27_contended sweep on one TPU v5e: host
    # input preparation, then the start of the region plan with its Pallas
    # admission rounds (op names shortened, host events under 100 us cut)
    path = cells.BENCH / "tests" / "data" / "trace_r27_plan.json"
    return tr.Trace.from_json(path.read_text())


def test_recorded_trace_program_times_and_busy_union():
    t = _recorded()
    assert t.window_s == pytest.approx(0.218671973)
    assert tr.total_s(tr.matching(t.modules, "_plan_scan")) == pytest.approx(
        0.025)
    assert tr.busy_s(t) == pytest.approx(0.024999563)
    assert tr.busy_s(t) <= tr.total_s(tr.matching(t.modules, "_plan_scan"))


def test_recorded_trace_idle_gaps_and_ops():
    t = _recorded()
    gaps = tr.idle_gaps(t)
    assert gaps[0] == ["Transpose", pytest.approx(0.193672384)]
    assert sum(g[1] for g in gaps) <= t.window_s - tr.busy_s(t) + 1e-9
    top = tr.top_ops(t)
    assert top[0][0].startswith("jit__plan_scan/%body.6")
    assert top[0][1] == pytest.approx(0.008176342)


def test_recorded_trace_admission_rounds():
    t = _recorded()
    ctx = _ctx(t)
    ctx.dims["R"] = 27
    mod = cells.reader("admission_roofline_pct")
    rounds = tr.inside(tr.matching(t.ops, mod.MATCH),
                       tr.matching(t.modules, mod.PROGRAM))
    assert len(rounds) == 211
    share = mod.read(ctx)
    need = 211 * 4 * 100_000 * 33 / 819e9
    assert share == pytest.approx(100 * need / tr.total_s(rounds))
    assert 0 < share < 100
