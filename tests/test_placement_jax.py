"""JAX placement planner: parity against the NumPy (N, R) batch kernel.

`plan_jax` must reproduce `PlacementEngine.plan` — which is itself
pinned bit-compatible to the greedy scalar reference — to 1e-6, with
epoch-by-epoch region assignments exactly equal (a single divergent
move would cascade through occupancy and dwell state). The tight-cap
case forces the ranked-admission path (preference rounds with denials);
the loose-cap case exercises the all-admitted fast path.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.carbon.intensity import TraceProvider  # noqa: E402
from repro.cluster.placement import PlacementConfig, PlacementEngine  # noqa: E402
from repro.cluster.placement_jax import plan_jax  # noqa: E402
from repro.cluster.slices import paper_family  # noqa: E402
from repro.workload.azure_like import sample_population  # noqa: E402

TOL = 1e-6
DAYS = 1
REGIONS = ("PL", "NL", "CAISO")


def _inputs(n, seed=5):
    provs = [TraceProvider.for_region(r, hours=24 * DAYS, seed=1)
             for r in REGIONS]
    traces = [t.util for t in sample_population(n, days=DAYS, seed=seed)]
    demand = np.stack(traces, axis=1)
    rng = np.random.default_rng(seed)
    state_gb = rng.choice([0.25, 1.0, 4.0], size=n)
    return provs, demand, state_gb


def _assert_plans_equal(p_np, p_j, ctx=""):
    assert (p_np.assign == p_j.assign).all(), f"{ctx}: assignments differ"
    assert (p_np.migrations == p_j.migrations).all(), ctx
    if p_np.migrations.size:
        assert float(np.abs(p_np.overhead_g - p_j.overhead_g).max()) <= TOL, ctx
        assert float(np.abs(p_np.downtime_s - p_j.downtime_s).max()) <= TOL, ctx
    assert (p_np.initial == p_j.initial).all(), ctx


@pytest.mark.parametrize("capacity", [None, 8],
                         ids=["uncapped", "tight-cap"])
def test_plan_jax_matches_numpy(capacity):
    n = 18
    provs, demand, state_gb = _inputs(n)
    eng = PlacementEngine(
        paper_family(), provs, region_names=REGIONS,
        config=PlacementConfig(capacity=capacity, min_dwell=4,
                               hysteresis=0.10))
    p_np = eng.plan(demand, state_gb=state_gb)
    p_j = plan_jax(eng, demand, state_gb=state_gb)
    _assert_plans_equal(p_np, p_j, ctx=f"cap={capacity}")
    if capacity is not None:
        assert int((p_j.occupancy() > capacity).sum()) == 0
    # the tight cap must actually exercise admission pressure somewhere
    if capacity is not None:
        assert p_j.migrations.sum() > 0


def test_plan_jax_respects_initial_assignment():
    n = 9
    provs, demand, state_gb = _inputs(n, seed=7)
    eng = PlacementEngine(paper_family(), provs, region_names=REGIONS,
                          config=PlacementConfig(min_dwell=2))
    initial = np.array([2, 2, 2, 1, 1, 1, 0, 0, 0])
    p_np = eng.plan(demand, state_gb=state_gb, initial=initial)
    p_j = plan_jax(eng, demand, state_gb=state_gb, initial=initial)
    _assert_plans_equal(p_np, p_j, ctx="initial")
    assert (p_j.initial == initial).all()


def test_plan_jax_empty_fleet():
    """N=0 short-circuits without tracing the round loop (regression:
    the scan used to trace (0, R) shapes and fall over inside argmax)."""
    provs = [TraceProvider.for_region(r, hours=24 * DAYS, seed=1)
             for r in REGIONS]
    eng = PlacementEngine(paper_family(), provs, region_names=REGIONS,
                          config=PlacementConfig(capacity=8, min_dwell=4))
    demand = np.zeros((288 * DAYS, 0))
    p_np = eng.plan(demand, state_gb=np.zeros(0))
    p_j = plan_jax(eng, demand, state_gb=np.zeros(0))
    _assert_plans_equal(p_np, p_j, ctx="N=0")
    assert p_j.assign.shape == (288 * DAYS, 0)
    assert p_j.migrations.shape == (0,)


def test_plan_jax_single_region():
    """R=1 short-circuits: with one region there is nothing to migrate
    to, so the plan is the frozen initial assignment."""
    provs = [TraceProvider.for_region("PL", hours=24 * DAYS, seed=1)]
    traces = [t.util for t in sample_population(7, days=DAYS, seed=11)]
    demand = np.stack(traces, axis=1)
    eng = PlacementEngine(paper_family(), provs, region_names=("PL",),
                          config=PlacementConfig(min_dwell=4))
    p_np = eng.plan(demand, state_gb=1.0)
    p_j = plan_jax(eng, demand, state_gb=1.0)
    _assert_plans_equal(p_np, p_j, ctx="R=1")
    assert int(p_j.migrations.sum()) == 0
    assert (p_j.assign == 0).all()


def test_plan_jax_rejects_unknown_admission_impl():
    provs, demand, state_gb = _inputs(4)
    eng = PlacementEngine(paper_family(), provs, region_names=REGIONS)
    with pytest.raises(ValueError, match="admission_impl"):
        plan_jax(eng, demand, state_gb=state_gb, admission_impl="cuda")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("n,block_n", [(18, 8192), (2500, 1024)],
                         ids=["one-block", "multi-block"])
def test_admission_impl_parity(impl, n, block_n):
    """The admission_impl dispatch: both backends must reproduce the
    NumPy planner exactly under tight capacity (every epoch runs denial
    rounds). The multi-block case runs the pallas grid over three
    1024-container blocks, the last one padded, so the cross-block SMEM
    counter carry is exercised; the xla impl ignores block_n (same
    dispatch surface either way)."""
    provs, demand, state_gb = _inputs(n, seed=13)
    cap = 7 * n // 18
    eng = PlacementEngine(
        paper_family(), provs, region_names=REGIONS,
        config=PlacementConfig(capacity=cap, min_dwell=4, hysteresis=0.10))
    p_np = eng.plan(demand, state_gb=state_gb)
    p_j = plan_jax(eng, demand, state_gb=state_gb,
                   admission_impl=impl, block_n=block_n)
    _assert_plans_equal(p_np, p_j, ctx=f"impl={impl} block={block_n}")
    assert int((p_j.occupancy() > cap).sum()) == 0


def test_plan_jax_carbon_matrix_feeds_fleet():
    """The planned carbon matrix drives a placed fleet run identically
    to the NumPy plan's (same plan => same matrix)."""
    n = 6
    provs, demand, state_gb = _inputs(n, seed=9)
    eng = PlacementEngine(paper_family(), provs, region_names=REGIONS,
                          config=PlacementConfig(capacity=4, min_dwell=4))
    p_np = eng.plan(demand, state_gb=state_gb)
    p_j = plan_jax(eng, demand, state_gb=state_gb)
    assert np.array_equal(p_np.carbon_matrix(), p_j.carbon_matrix())


def test_preference_ranks_match_f64_argmax():
    """The admission kernel's integer view of the epoch's net table: for
    every strike mask, the un-struck region with the smallest rank is
    the f64 argmax over un-struck regions (first max on ties), and it
    ranks below R iff that net saving is positive."""
    import jax
    import jax.numpy as jnp

    from repro.cluster.placement_pallas import preference_ranks
    R = 3
    # few distinct values: ties and non-positive nets everywhere
    net = np.random.default_rng(0).choice([-1.0, 0.0, 0.5, 2.0],
                                          size=(4000, R))
    with jax.enable_x64(True):
        pref = np.asarray(preference_ranks(jnp.asarray(net)))
    assert pref.shape == (R, 4000) and pref.dtype == np.int32
    for mask in range(2 ** R):
        struck = np.array([(mask >> r) & 1 for r in range(R)], dtype=bool)
        net_eff = np.where(struck[None, :], -np.inf, net)
        want = net_eff.max(axis=1) > 0.0
        rank = np.where(struck[:, None], R, pref)
        assert ((rank.min(axis=0) < R) == want).all(), mask
        assert (rank.argmin(axis=0)[want]
                == net_eff.argmax(axis=1)[want]).all(), mask
