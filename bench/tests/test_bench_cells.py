"""The benchmark's files are found by name, and BENCHMARK.json keeps to the
contract's shape and character rules."""
import json
import re

import pytest

from bench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return cells.load()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len((cells.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_and_units(bench):
    entries = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
               + bench["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    texts = ([e["why"] for e in bench["configs"] + bench["workloads"]]
             + [m["layer"] for m in bench["per_layer"]]
             + [c["source"] for c in bench["configs"]] + bench["command"])
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_finds_its_config_and_mix(bench):
    pairs = set()
    for w in bench["workloads"]:
        cfg = cells.config(bench, w["config"])
        mix = cells.mix(w["traffic"])
        assert cfg["name"] == w["config"] and mix["name"] == w["traffic"]
        assert w["chips"] == 1
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert cells.config(bench, c["name"])["reduced"] == c["reduced"]


def test_unknown_names_are_refused(bench):
    with pytest.raises(KeyError):
        cells.workload(bench, "no_such_cell")
    with pytest.raises(KeyError):
        cells.mix("no_such_mix")
    with pytest.raises(KeyError):
        cells.reader("no_such_metric")


@pytest.mark.parametrize("name", ["plan_ms", "admission_roofline_pct",
                                  "fleet_scan_ms", "device_idle_pct",
                                  "compile_s", "gen_s"])
def test_reader_names_its_layer_unit_and_moves(bench, name):
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = cells.reader(name)
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}


def test_per_layer_metrics_of_each_cell(bench):
    got = {w["name"]: {m["name"] for m in cells.per_layer(bench, w["name"])}
           for w in bench["workloads"]}
    for names in got.values():
        assert {"plan_ms", "fleet_scan_ms", "device_idle_pct",
                "admission_roofline_pct", "compile_s", "gen_s"} <= names


def test_a_new_metric_without_workloads_follows_its_moves(bench):
    extra = json.loads(json.dumps(bench))
    extra["per_layer"].append({"name": "x_ms", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "device",
                               "moves": "setup_s"})
    assert all("x_ms" in {m["name"] for m in cells.per_layer(extra, w["name"])}
               for w in extra["workloads"])


def test_every_listed_cell_and_reader_exists(bench):
    names = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"] + bench["end_to_end"]:
        assert set(m.get("workloads", [])) <= names, m["name"]
    for m in bench["per_layer"]:
        assert cells.reader(m["name"]).read


def test_a_listed_metric_that_reads_nothing_fails_the_run(bench,
                                                           monkeypatch):
    from types import SimpleNamespace

    from bench import run
    silent = SimpleNamespace(read=lambda ctx: None)
    real = cells.reader
    monkeypatch.setattr(cells, "reader", lambda n: silent
                        if n == "plan_ms" else real(n))
    ctx = SimpleNamespace(trace=None, peaks={}, dims={},
                          setup={"compile_s": 1.0, "gen_s": 2.0})
    with pytest.raises(RuntimeError, match="plan_ms"):
        run.layer_metrics(bench, "r3_placed", ctx)
