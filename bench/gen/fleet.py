"""One cell's inputs, made from `--seed` and the configuration's sizes.

Every seed gives the same sizes (traces x epochs x regions); only the
values differ. The program receives the arrays made here and seeds for the
layers that draw their own noise (user population, fault masks).
"""
from __future__ import annotations

import numpy as np

from bench.gen.azure_like import INTERVAL_S, sample_population_matrix
from bench.gen.carbon_traces import synth_trace

STREAMS = ("traces", "carbon", "population", "faults")


def stream_seeds(seed: int) -> dict:
    """A 32-bit seed per input stream, derived from the run's seed (which
    may be any non-negative integer, larger than 32 bits included)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    kids = np.random.SeedSequence(seed).spawn(len(STREAMS))
    return {name: int(k.generate_state(1)[0]) for name, k in zip(STREAMS, kids)}


def region_matrix(regions, days: int, seed: int) -> np.ndarray:
    """(T, R) carbon intensity at each 5-minute epoch: one hourly synthetic
    trace per region, held for the hour, as the carbon providers do."""
    T = int(days * 24 * 3600 / INTERVAL_S)
    hour = (np.arange(T, dtype=np.float64) * INTERVAL_S // 3600.0).astype(np.int64)
    return np.stack([synth_trace(r, 24 * days, seed)[hour] for r in regions],
                    axis=1)


def make_inputs(cfg: dict, seed: int) -> dict:
    """Demand traces (T, n_traces), region carbon (T, R), the targets and
    the per-layer seeds of one run."""
    seeds = stream_seeds(seed)
    days = int(cfg["days"])
    return {
        "traces": sample_population_matrix(int(cfg["n_traces"]), days=days,
                                           seed=seeds["traces"]),
        "regions": region_matrix(cfg["regions"], days, seeds["carbon"]),
        "targets": [float(t) for t in np.linspace(
            cfg["target_lo"], cfg["target_hi"], int(cfg["n_targets"]))],
        "seeds": seeds,
    }
