"""One cell's inputs, made from `--seed` and the configuration's sizes.

Every seed gives the same sizes (traces x epochs x regions); only the
values differ. The program receives the arrays made here and seeds for the
layers that draw their own noise (user population, fault masks). Each
layer a mix turns on adds its own inputs (`bench.layers`).
"""
from __future__ import annotations

import zlib

import numpy as np

from bench import cells
from bench.gen.azure_like import INTERVAL_S, sample_population_matrix
from bench.gen.carbon_traces import synth_trace

# Spawned children depend on their index alone: a stream appended here
# leaves the seeds of those before it as they were.
STREAMS = ("traces", "carbon", "population", "faults")
# first word of the spawn key of a stream named after a layer; the spawned
# streams' keys have one word, so the two never meet
_LAYER_KEY = 0x1A7E5


def stream_seeds(seed: int, layers=()) -> dict:
    """A 32-bit seed per input stream, derived from the run's seed (which
    may be any non-negative integer, larger than 32 bits included), and
    one for each of `layers` that is not a stream already, keyed by the
    layer's name, so that no order of layers moves another's seed."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    names = STREAMS + tuple(n for n in layers if n not in STREAMS)
    kids = np.random.SeedSequence(seed).spawn(len(STREAMS)) + [
        np.random.SeedSequence(seed, spawn_key=(_LAYER_KEY,
                                                zlib.crc32(n.encode())))
        for n in names[len(STREAMS):]]
    return {name: int(k.generate_state(1)[0]) for name, k in zip(names, kids)}


def region_matrix(regions, days: int, seed: int) -> np.ndarray:
    """(T, R) carbon intensity at each 5-minute epoch: one hourly synthetic
    trace per region, held for the hour, as the carbon providers do."""
    T = int(days * 24 * 3600 / INTERVAL_S)
    hour = (np.arange(T, dtype=np.float64) * INTERVAL_S // 3600.0).astype(np.int64)
    return np.stack([synth_trace(r, 24 * days, seed)[hour] for r in regions],
                    axis=1)


def make_inputs(cfg: dict, seed: int, mix: dict = None) -> dict:
    """Demand traces (T, n_traces), region carbon (T, R), the targets and
    the per-layer seeds of one run, with the parameters (`layers`, as the
    mix gives them, for the reference) and the inputs of each layer `mix`
    turns on."""
    layers = cells.layers(mix) if mix else {}
    seeds = stream_seeds(seed, tuple(layers))
    days = int(cfg["days"])
    out = {
        "traces": sample_population_matrix(int(cfg["n_traces"]), days=days,
                                           seed=seeds["traces"]),
        "regions": region_matrix(cfg["regions"], days, seeds["carbon"]),
        "targets": [float(t) for t in np.linspace(
            cfg["target_lo"], cfg["target_hi"], int(cfg["n_targets"]))],
        "seeds": seeds,
        "layers": dict(mix["layers"]) if mix else {},
    }
    for name, layer in layers.items():
        got = layer.inputs(cfg, mix["layers"][name], seeds)
        clash = sorted(set(got) & set(out))
        if clash:
            raise ValueError(f"layer {name!r} makes inputs {clash}, which "
                             f"the cell has already")
        out.update(got)
    return out
