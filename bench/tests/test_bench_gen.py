"""The benchmark's copy of the input generators keeps the program
generator's calibration statistics, and is fixed by the seed."""
import numpy as np
import pytest

from bench.gen.azure_like import population_stats, sample_population_matrix
from bench.gen.carbon_traces import synth_trace, trace_cov
from bench.gen.fleet import make_inputs, stream_seeds
from bench.gen.regions import REGIONS


def test_matrix_generator_matches_azure_stats():
    mat = sample_population_matrix(1000, days=3, seed=0)
    assert mat.shape == (3 * 288, 1000)
    assert mat.min() >= 0.0 and mat.max() <= 1.0
    stats = population_stats(mat)
    assert abs(stats["frac_cov_below_0.25"] - 0.08) < 0.08
    assert stats["frac_cov_above_0.4"] > 0.5
    assert abs(stats["frac_cov_above_1.0"] - 0.30) < 0.10
    assert abs(stats["frac_mean_below_0.10"] - 0.43) < 0.12


@pytest.mark.parametrize("region", ["PL", "NL", "CAISO"])
def test_synthetic_carbon_traces_hit_target_cov(region):
    tr = synth_trace(region, hours=24 * 120, seed=0)
    assert (tr > 0).all()
    got, want = trace_cov(tr), REGIONS[region].cov
    assert abs(got - want) / want < 0.25, (got, want)


def test_inputs_are_fixed_by_a_large_seed():
    cfg = {"n_traces": 64, "days": 1, "regions": ["PL", "NL", "CAISO"],
           "target_lo": 20.0, "target_hi": 80.0, "n_targets": 10}
    seed = 2**33 + 12345
    a, b = make_inputs(cfg, seed), make_inputs(cfg, seed)
    c = make_inputs(cfg, seed + 1)
    assert a["traces"].shape == (288, 64) and a["regions"].shape == (288, 3)
    assert np.array_equal(a["traces"], b["traces"])
    assert np.array_equal(a["regions"], b["regions"])
    assert a["seeds"] == b["seeds"] != c["seeds"]
    assert not np.array_equal(a["traces"], c["traces"])
    assert all(0 <= s < 2**32 for s in stream_seeds(seed).values())
    # the regions' carbon is held for each hour, as the providers do
    assert np.array_equal(a["regions"][:12], np.repeat(a["regions"][:1], 12, 0))
