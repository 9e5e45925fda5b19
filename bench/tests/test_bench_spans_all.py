"""Readers of the all-layer path's spans and programs: `layer_prep_ms`,
`elastic_transfer_ms` and `elastic_scan_ms`, on a trace with hand-placed
spans; each reads nothing where its instrumentation is missing, as in a
program from before the spans. `r3_all` lists `elastic_scan_ms` and the
six metrics of `r3_placed`."""
from types import SimpleNamespace

import pytest

from bench import cells
from bench import trace as tr

MS = 1_000_000
NEW = ("elastic_scan_ms", "layer_prep_ms", "elastic_transfer_ms")


def _layered():
    """A traced all-layer sweep, hand placed: the layers' preparation, the
    elasticity scan's transfers and its program."""
    return tr.Trace(window=(0, 100 * MS), host=[
        ("sweep", 0, 100 * MS),
        ("sweep.prepare", 0, 5 * MS),
        ("sweep.faults", 1 * MS, 2 * MS),
        ("sweep.traffic", 42 * MS, 1 * MS),
        ("sweep.energy", 43 * MS, 2 * MS),
        ("sweep.elastic_budget", 45 * MS, 1 * MS),
        ("sweep.elastic", 46 * MS, 30 * MS),
        ("elastic.prepare", 46 * MS, 3 * MS),
        ("elastic.h2d", 49 * MS, 4 * MS),
        ("elastic.wait", 53 * MS, 20 * MS),
        ("elastic.d2h", 73 * MS, 2 * MS)],
        modules=[("jit__elastic_scan(7)", 53 * MS, 19 * MS, 0)])


def _read(name, t):
    ctx = SimpleNamespace(trace=t, peaks={}, setup={},
                          dims={"n_traces": 100, "n_targets": 10, "T": 288,
                                "R": 3})
    return cells.reader(name).read(ctx)


def test_layer_readers_on_hand_placed_spans():
    t = _layered()
    # faults 2 + traffic 1 + energy 2 + elastic budget 1 + prepare 3
    assert _read("layer_prep_ms", t) == pytest.approx(9.0)
    assert _read("elastic_transfer_ms", t) == pytest.approx(6.0)
    assert _read("elastic_scan_ms", t) == pytest.approx(19.0)


@pytest.mark.parametrize("drop, silent", [
    ("sweep.faults", "layer_prep_ms"), ("elastic.prepare", "layer_prep_ms"),
    ("sweep.traffic", "layer_prep_ms"), ("elastic.h2d", "elastic_transfer_ms"),
    ("elastic.d2h", "elastic_transfer_ms")])
def test_layer_readers_read_nothing_without_their_spans(drop, silent):
    t = _layered()
    t.host = [e for e in t.host if e[0] != drop]
    got = {n: _read(n, t) for n in ("layer_prep_ms", "elastic_transfer_ms")}
    assert {n for n, v in got.items() if v is None} == {silent}


def test_elastic_scan_reads_nothing_without_its_program():
    t = _layered()
    t.modules = []
    assert _read("elastic_scan_ms", t) is None


def test_elastic_scan_names_its_layer_unit_and_moves():
    bench = cells.load()
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "elastic_scan_ms")
    mod = cells.reader("elastic_scan_ms")
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["workloads"] == ["r3_all"]


@pytest.mark.parametrize("name", ["layer_prep_ms", "elastic_transfer_ms"])
def test_span_readers_fit_the_benchmark(name):
    bench = cells.load()
    mod = cells.reader(name)
    assert mod.MOVES in {m["name"] for m in bench["end_to_end"]}
    assert mod.UNIT == "ms" and mod.LAYER and "\n" not in mod.LAYER


def test_the_all_layer_cell_reads_its_layers_and_the_placed_metrics():
    bench = cells.load()
    names = {m["name"] for m in cells.per_layer(bench, "r3_all")}
    assert names >= {"elastic_scan_ms", "plan_ms", "admission_roofline_pct",
                     "fleet_scan_ms", "device_idle_pct", "compile_s",
                     "gen_s"}
    placed = {m["name"] for m in cells.per_layer(bench, "r3_placed")}
    assert not placed & set(NEW)
