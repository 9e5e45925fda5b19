"""Cross-process RNG seed stability (regression).

Both `repro.carbon.traces.synth_trace` and
`repro.models.params.init_params` used Python's `hash()` to derive
per-region / per-parameter-path salts. `str.__hash__` is salted per
process (PYTHONHASHSEED), so two runs of the same program generated
*different* carbon traces and parameter inits. The fix derives salts
from `zlib.crc32` instead; these tests pin concrete values so any
future drift back to an unstable digest (or an accidental change to
the salt formula, which silently invalidates every recorded benchmark
number) fails loudly.
"""
import numpy as np
import pytest

from repro.carbon.traces import synth_trace

# pinned against the crc32 salts (seed + crc32(name) % 100003)
TRACE_PINS = {
    "PL": (781.28, 751.7028384188773, 755.0008735220761,
           36423.42441028709),
    "NL": (444.00000000000006, 416.3211317714321, 380.2188895888865,
           20042.12321868904),
    "CAISO": (285.2, 251.25460011654013, 255.01356311774492,
              11426.162141202218),
}


@pytest.mark.parametrize("region", sorted(TRACE_PINS))
def test_synth_trace_pinned_values(region):
    tr = synth_trace(region, hours=48, seed=0)
    v0, v7, v33, vsum = TRACE_PINS[region]
    assert tr[0] == pytest.approx(v0, rel=0, abs=1e-9)
    assert tr[7] == pytest.approx(v7, rel=0, abs=1e-9)
    assert tr[33] == pytest.approx(v33, rel=0, abs=1e-9)
    assert tr.sum() == pytest.approx(vsum, rel=0, abs=1e-6)


def test_synth_trace_distinct_per_region_same_seed():
    # the whole point of the per-region salt: same seed, different
    # realizations (identical CoV-calibrated *statistics* are covered
    # by the carbon-core suite)
    a = synth_trace("PL", hours=48, seed=0)
    b = synth_trace("NL", hours=48, seed=0)
    assert not np.allclose(a / a.mean(), b / b.mean())


def test_init_params_pinned_values():
    jax = pytest.importorskip("jax")
    from repro.models.params import ParamSpec, init_params
    tree = {"w": ParamSpec((4, 3), ("a", "b")),
            "blk": {"b": ParamSpec((5,), ("a",), init="normal")}}
    p = init_params(tree, jax.random.PRNGKey(0))
    w = np.asarray(p["w"], dtype=np.float64)
    b = np.asarray(p["blk"]["b"], dtype=np.float64)
    # pinned against crc32("w") / crc32("blk/b") fold_in salts, with
    # jax's default partitionable threefry (`jax_threefry_partitionable`
    # is on by default since jax 0.5; the bits differ from the old
    # default, so these pins are for that PRNG)
    assert w.sum() == pytest.approx(0.13009770726785064, abs=1e-7)
    assert w[0, 0] == pytest.approx(0.010057304054498672, abs=1e-7)
    assert b.sum() == pytest.approx(0.07656742027029395, abs=1e-7)
    # per-path folding: distinct leaves draw distinct streams
    assert not np.allclose(w[:5].ravel()[: b.size], b)
