"""A cell turns a layer on by adding files: a layer, a mix and a reference
added to a copy of `bench/`, with no file there edited, run through
`run_cell` on the CPU at 240 traces. The harness refuses, before set-up, a
mix that turns on a layer its reference does not model, and it refuses a
layer with no file and a keyword set twice."""
import hashlib
import json
import shutil
import time

import jax
import pytest

from bench import cells, run
from bench.gen import fleet

SIZES = {"n_traces": 240}
SEED = 2**33 + 161

SCALED = '''"""Demand scaled by the mix's `scale`: `SweepSpec.demand_scale`."""
import numpy as np


def inputs(cfg, params, seeds):
    return {"scaled_draw": np.random.default_rng(seeds["scaled"]).random(3)}


def program(cfg, params, inputs):
    return {"demand_scale": params["scale"]}
'''
ALSO_SCALED = '''"""A second layer that sets the demand scale."""


def inputs(cfg, params, seeds):
    return {}


def program(cfg, params, inputs):
    return {"demand_scale": params["scale"]}
'''
SETS_SIM = '''"""A layer that sets a keyword of the placed sweep."""


def inputs(cfg, params, seeds):
    return {}


def program(cfg, params, inputs):
    return {"sim": None}
'''
REF = '''"""Placement on demand scaled by the mix's `scale`."""
from bench.ref import placed

LAYERS = {layers!r}
COUNT_KEYS = placed.COUNT_KEYS


def sweep(cfg, inputs, targets):
    scale = {scale}
    return placed.sweep(cfg, {{**inputs, "traces": inputs["traces"] * scale}},
                        targets)
'''
APPLIES = "inputs['layers']['scaled']['scale']"


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*")) if p.is_file()}


@pytest.fixture
def copy(tmp_path, monkeypatch):
    """A copy of `bench/` that `cells` reads; on leaving, every file that
    was there is as it was."""
    root = shutil.copytree(cells.BENCH, tmp_path / "bench",
                           ignore=shutil.ignore_patterns("__pycache__",
                                                         "tests"))
    before = _digest(root)
    monkeypatch.setattr(cells, "BENCH", root)
    yield root
    after = _digest(root)
    assert {k: after.get(k) for k in before} == before


def _add(root, files: dict):
    for rel, text in files.items():
        path = root / rel
        assert not path.exists(), rel
        path.write_text(text)


def _mix(root, name, layers, reference=None):
    mix = {"name": name, "what": "a test mix", "check_targets": 2,
           "layers": layers}
    if reference:
        mix["reference"] = reference
    _add(root, {f"mixes/{name}.json": json.dumps(mix)})


def _run(traffic):
    bench = cells.load()
    bench["workloads"].append({"name": "r3_test", "config": "fleet_1m_r3",
                               "traffic": traffic, "chips": 1,
                               "why": "a test cell"})
    return run.run_cell(bench, "r3_test", SEED, 0.0, False, jax.devices(),
                        {}, time.perf_counter(), sizes=SIZES)


def test_a_layer_mix_and_reference_added_as_files_run_correct(copy):
    _add(copy, {"layers/scaled.py": SCALED,
                "ref/scaled.py": REF.format(layers=("scaled",),
                                            scale=APPLIES)})
    _mix(copy, "scaled", {"scaled": {"scale": 1.01}}, "scaled")
    out = _run("scaled")
    assert out["correct"], out["checks"]
    assert out["checks"]["row_rel_gap"]["value"] < 1e-12
    assert out["checks"]["plan_mismatches"]["value"] == 0


def test_a_reference_that_ignores_the_layer_is_not_correct(copy):
    _add(copy, {"layers/scaled.py": SCALED,
                "ref/ignores.py": REF.format(layers=("scaled",), scale=1.0)})
    _mix(copy, "scaled", {"scaled": {"scale": 1.01}}, "ignores")
    out = _run("scaled")
    assert not out["correct"], out["checks"]


def test_a_layer_the_reference_does_not_model_is_refused_before_set_up(
        copy, monkeypatch):
    _add(copy, {"layers/scaled.py": SCALED})
    _mix(copy, "scaled", {"scaled": {"scale": 1.01}})   # reference: placed

    def set_up(*a, **k):
        raise AssertionError("set-up started")
    monkeypatch.setattr(fleet, "make_inputs", set_up)
    with pytest.raises(ValueError, match="'scaled'"):
        _run("scaled")


def test_a_layer_with_no_file_is_refused(copy):
    _add(copy, {"ref/ghost.py": REF.format(layers=("ghost",), scale=1.0)})
    _mix(copy, "ghost", {"ghost": {}}, "ghost")
    with pytest.raises(KeyError, match="no layer 'ghost'"):
        _run("ghost")


@pytest.mark.parametrize("second, text, key", [
    ("also_scaled", ALSO_SCALED, "demand_scale"),
    ("sets_sim", SETS_SIM, "sim"),
])
def test_a_keyword_set_twice_is_refused(copy, second, text, key):
    _add(copy, {"layers/scaled.py": SCALED, f"layers/{second}.py": text,
                "ref/both.py": REF.format(layers=("scaled", second),
                                          scale=APPLIES)})
    _mix(copy, "both", {"scaled": {"scale": 1.01}, second: {"scale": 1.02}},
         "both")
    with pytest.raises(ValueError, match=f"sets '{key}'"):
        _run("both")


def test_a_layer_draws_from_a_stream_of_its_own_name():
    seed = 2**35 + 3
    alone = fleet.stream_seeds(seed)
    ab = fleet.stream_seeds(seed, ("a", "faults", "b"))
    assert ab == fleet.stream_seeds(seed, ("b", "a"))
    assert {k: ab[k] for k in alone} == alone        # the four stay as they were
    assert len({ab["a"], ab["b"], *alone.values()}) == len(alone) + 2
