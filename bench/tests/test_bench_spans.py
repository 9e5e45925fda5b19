"""Readers of the program's host spans and counters (`repro.obs`):
`host_prep_ms`, `h2d_ms`, `d2h_ms`, `aggregate_ms` and `admission_rounds`,
on a trace with hand-placed spans and on a small sweep traced on the CPU."""
import glob
import re
from types import SimpleNamespace

import jax
import pytest

from bench import cells, run
from bench import trace as tr

MS = 1_000_000
SPANS = ("host_prep_ms", "h2d_ms", "d2h_ms", "aggregate_ms")
COUNTS = ("admission_rounds", "h2d_gb", "d2h_gb")
READERS = SPANS + COUNTS


def _synthetic():
    # one sweep over a 100 ms window; a fleet.h2d of the sweep before lies
    # outside it, and sweep.aggregate runs 10 ms past its end
    return tr.Trace(window=(0, 100 * MS), host=[
        ("fleet.h2d", -20 * MS, 10 * MS),
        ("sweep", 0, 100 * MS),
        ("sweep.prepare", 0, 40 * MS),
        ("plan", 5 * MS, 30 * MS),
        ("plan.prepare", 5 * MS, 3 * MS),
        ("plan.h2d", 8 * MS, 4 * MS),
        ("plan.wait", 12 * MS, 18 * MS),
        ("plan.d2h", 30 * MS, 5 * MS),
        ("fleet.prepare", 40 * MS, 2 * MS),
        ("fleet.h2d", 42 * MS, 8 * MS),
        ("fleet.wait", 50 * MS, 30 * MS),
        ("fleet.d2h", 80 * MS, 5 * MS),
        ("fleet.result", 85 * MS, 3 * MS),
        ("sweep.aggregate", 88 * MS, 22 * MS),
        ("$fleet.py:1096 _aggregate_sweep_rows", 88 * MS, 12 * MS)])


def _ctx(t, T=288):
    return SimpleNamespace(trace=t, peaks={}, setup={},
                           dims={"n_traces": 100, "n_targets": 10, "T": T,
                                 "R": 3})


def _read(name, ctx):
    return cells.reader(name).read(ctx)


def test_span_readers_on_hand_placed_spans():
    ctx = _ctx(_synthetic())
    # sweep.prepare's own 40 - 30 ms (less its plan), plan.prepare 3,
    # fleet.prepare 2
    assert _read("host_prep_ms", ctx) == pytest.approx(15.0)
    # plan.h2d 4 + fleet.h2d 8; the earlier sweep's push is outside
    assert _read("h2d_ms", ctx) == pytest.approx(12.0)
    assert _read("d2h_ms", ctx) == pytest.approx(10.0)
    # fleet.result 3 + sweep.aggregate clipped to the window's end, 12
    assert _read("aggregate_ms", ctx) == pytest.approx(15.0)


def test_off_path_layers_count_as_host_preparation():
    t = _synthetic()
    t.host += [("sweep.traffic", 42 * MS, 1 * MS),
               ("sweep.energy", 43 * MS, 2 * MS),
               ("sweep.elastic_budget", 45 * MS, 4 * MS)]
    assert _read("host_prep_ms", _ctx(t)) == pytest.approx(22.0)


@pytest.mark.parametrize("drop", [None, "sweep.prepare", "fleet.prepare",
                                  "fleet.h2d", "fleet.d2h", "fleet.result",
                                  "sweep.aggregate"])
def test_span_readers_read_nothing_without_their_spans(drop):
    t = _synthetic()
    t.host = [e for e in t.host if drop is not None and e[0] != drop]
    got = {n: _read(n, _ctx(t)) for n in SPANS}
    if drop is None:                      # no span of the program at all
        assert got == dict.fromkeys(SPANS)
    else:
        want_none = {"sweep.prepare": {"host_prep_ms"},
                     "fleet.prepare": {"host_prep_ms"},
                     "fleet.h2d": {"h2d_ms"}, "fleet.d2h": {"d2h_ms"},
                     "fleet.result": {"aggregate_ms"},
                     "sweep.aggregate": {"aggregate_ms"}}[drop]
        assert {n for n, v in got.items() if v is None} == want_none


def test_admission_rounds_reads_the_program_counter():
    from repro import obs
    with obs.sweep():
        obs.count("admission_rounds", 576)
    assert _read("admission_rounds", _ctx(None)) == pytest.approx(2.0)
    with obs.sweep():                     # a sweep with no admission
        pass
    assert _read("admission_rounds", _ctx(None)) is None


def test_transfer_volumes_read_the_program_counters():
    from repro import obs
    with obs.sweep():
        obs.count("h2d_bytes", 600_000_000)
        obs.count("d2h_bytes", 190_000_000)
    assert _read("h2d_gb", _ctx(None)) == pytest.approx(0.6)
    assert _read("d2h_gb", _ctx(None)) == pytest.approx(0.19)
    # each reads its own layer's counter, the layer of its time metric
    for gb, ms in (("h2d_gb", "h2d_ms"), ("d2h_gb", "d2h_ms")):
        assert cells.reader(gb).LAYER == cells.reader(ms).LAYER
    with obs.sweep():                     # a sweep that moved nothing
        pass
    assert _read("h2d_gb", _ctx(None)) is None
    assert _read("d2h_gb", _ctx(None)) is None


def test_readers_agree_with_the_benchmark():
    """Each reader's layer, unit and moved metric fit `BENCHMARK.json`:
    it moves one of its end-to-end metrics; a layer it already names is
    named letter for letter (the admission kernel's); names and units
    keep the benchmark's character set."""
    bench = cells.load()
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    for name in READERS:
        mod = cells.reader(name)
        assert mod.MOVES in e2e
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", mod.UNIT)
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name)
        assert mod.LAYER and "\n" not in mod.LAYER
    assert (cells.reader("admission_rounds").LAYER
            == layers["admission_roofline_pct"])
    assert {cells.reader(n).UNIT for n in SPANS} == {"ms"}


def test_readers_find_the_program_spans_of_a_sweep_traced_here(tmp_path):
    """A placed sweep of the cell's configuration at 240 traces, traced on
    the CPU as the harness traces its window: every reader reads, and the
    four span metrics together fit in the window."""
    from bench import sweep
    from bench.gen.fleet import make_inputs
    name = "r3_placed"
    cell = cells.workload(cells.load(), name)
    cfg = {**cells.config(cells.load(), cell["config"]), "n_traces": 240}
    inputs = make_inputs(cfg, 5)
    spec = sweep.program_sweep(cfg, cells.mix(cell["traffic"]), inputs)
    spec.run()                            # compile outside the window
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(run.SPAN):
        spec.run()
    jax.profiler.stop_trace()
    t = tr.from_xplane(sorted(glob.glob(
        f"{tmp_path}/plugins/profile/*/*.xplane.pb"))[-1], run.SPAN)
    T = inputs["traces"].shape[0]
    got = {n: _read(n, _ctx(t, T)) for n in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert sum(got[n] for n in SPANS) <= t.window_s * 1e3
    assert 1.0 <= got["admission_rounds"] <= len(cfg["regions"])
