"""Everything of one cell, found by name.

`BENCHMARK.json` names the cells; a cell's configuration is the file its
`configs` entry names, its traffic mix is `bench/mixes/<traffic>.json`, and
each per-layer metric is read by `bench/metrics/<name>.py`. A mix's
`layers` maps layer names to their parameters; each layer is
`bench/layers/<layer>.py` (its inputs and its program settings, see
`bench.layers`), and the mix's `reference` names `bench/ref/<reference>.py`
(`placed` where the mix names none), which recomputes the cell's answers
and lists the layers it models. A new cell, mix, layer, reference or
metric is a new file and a new entry, never an edit of a file.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


def load(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    entry = _by_name(bench["configs"], name, "config")
    return json.loads((ROOT / entry["file"]).read_text())


def mix(name: str) -> dict:
    path = BENCH / "mixes" / f"{name}.json"
    if not path.exists():
        raise KeyError(f"no traffic mix {name!r} ({path} is missing)")
    return json.loads(path.read_text())


def _module(kind: str, name: str, what: str):
    """`bench/<kind>/<name>.py`, loaded from its file."""
    path = BENCH / kind / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no {what} {name!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The module that reads per-layer metric `name` (bench/metrics)."""
    return _module("metrics", name, "reader for metric")


def reference(mix: dict):
    """The module that recomputes `mix`'s answers (bench/ref). Refuses a
    mix that turns on a layer the reference does not model."""
    name = mix.get("reference", "placed")
    ref = _module("ref", name, "reference")
    extra = sorted(set(mix["layers"]) - set(ref.LAYERS))
    if extra:
        raise ValueError(f"mix {mix['name']!r} turns on layers {extra}, "
                         f"which its reference {name!r} does not model")
    return ref


def layers(mix: dict) -> dict:
    """The modules of the layers `mix` turns on, by name (bench/layers)."""
    return {name: _module("layers", name, "layer") for name in mix["layers"]}


def end_to_end(bench: dict, cell: str) -> list:
    """The cell's end-to-end metric entries."""
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(bench: dict, cell: str) -> list:
    """The cell's per-layer metric entries: those that list the cell, or
    that list none and move an end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
