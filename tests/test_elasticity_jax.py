"""JAX elasticity scan: parity with the NumPy layer and the fleet sweep.

The scan mirrors `repro.core.elasticity` term for term (consuming the
same host-precomputed forecast and budget series), so allocated level
counts must be *identical* —
not merely close — on both the dense and indexed carbon layouts; float
streams get the backend parity budget (1e-6) though in practice they
agree to ~1e-13.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.fleet_jax import ensure_cpu_xla_flags  # noqa: E402

ensure_cpu_xla_flags()

from repro import obs  # noqa: E402
from repro.carbon.forecast import forecast_series  # noqa: E402
from repro.core.elasticity import (ElasticityConfig,  # noqa: E402
                                   simulate_elastic)
from repro.core.elasticity_jax import (_build_scan,  # noqa: E402
                                       _prepare, simulate_elastic_jax)

TOL = 1e-6
CFG = dict(k_levels=4, unit_capacity=1.5, base_w=50.0, peak_w=200.0,
           min_level=1, max_step=1)
FMODE = {"oracle": "oracle", "persistence": "persistence",
         "forecast": "diurnal_ar1"}
# budgets (g per epoch) at _inputs' size: none, binding in all but the
# zero-intensity epochs, binding in some epochs and not in others
BUDGETS = [None, 2.0, 100.0]


def _inputs(T=48, N=12, R=3, seed=0, case="random"):
    """Demand and carbon; `case` "ties" gives the second half of the
    containers the first half's demand and regions (equal scores, so the
    greedy's stable order decides between them), "zero_gram" zeroes the
    carbon in every fourth epoch (levels that cost nothing)."""
    rng = np.random.default_rng(seed)
    demand = np.abs(rng.normal(3.0, 1.5, (T, N)))
    region_mat = np.abs(rng.normal(300.0, 150.0, (T, R)))
    region_mat[5] = 0.0                      # zero-intensity epoch
    codes = rng.integers(0, R, (T, N)).astype(np.int32)
    if case == "ties":
        demand[:, N // 2:] = demand[:, :N - N // 2]
        codes[:, N // 2:] = codes[:, :N - N // 2]
    elif case == "zero_gram":
        region_mat[::4] = 0.0
    dense = region_mat[np.arange(T)[:, None], codes]
    return demand, region_mat, codes, dense


def _numpy_indexed(demand, region_mat, codes, cfg, dt):
    """The NumPy backend on the indexed layout: carbon forecast on the
    (T, R) region matrix, then gathered, as the JAX scan does."""
    T = demand.shape[0]
    period = max(1, int(round(24 * 3600.0 / dt)))
    chat = forecast_series(region_mat, FMODE[cfg.forecast],
                           period_steps=period, rho=cfg.rho)
    rows = np.arange(T)[:, None]
    return simulate_elastic(demand, region_mat[rows, codes], cfg, dt,
                            carbon_forecast=chat[rows, codes])


@pytest.mark.parametrize("case", ["random", "ties", "zero_gram"])
@pytest.mark.parametrize("budget", BUDGETS)
@pytest.mark.parametrize("mode", ["oracle", "persistence", "forecast"])
def test_jax_matches_numpy_dense_and_indexed(mode, budget, case):
    demand, region_mat, codes, dense = _inputs(case=case)
    cfg = ElasticityConfig(budget_g_per_epoch=budget, forecast=mode, **CFG)
    refs = (simulate_elastic(demand, dense, cfg, 300.0),
            _numpy_indexed(demand, region_mat, codes, cfg, 300.0))
    for a, carbon in zip(refs, (dense, (region_mat, codes))):
        with obs.sweep():
            b = simulate_elastic_jax(demand, carbon, cfg, 300.0,
                                     record=True)
            cut = obs.last_sweep()["elastic_cut_epochs"]
        np.testing.assert_array_equal(a.levels, b.levels)
        scale = max(float(np.max(np.abs(a.served_w))), 1.0)
        assert np.max(np.abs(a.served_w - b.served_w)) <= TOL * scale
        assert abs(a.emissions_g - b.emissions_g) <= TOL * max(
            abs(a.emissions_g), 1.0)
        assert a.cap_violations == b.cap_violations
        assert a.summary()["elastic_level_epochs"] \
            == b.summary()["elastic_level_epochs"]
        # the budgets bind as described at BUDGETS
        T = demand.shape[0]
        if budget is None:
            assert cut == 0
        elif budget == BUDGETS[1]:
            assert cut >= T // 2
        else:
            assert 0 < cut < T


def test_record_false_summary_matches_record_true():
    demand, region_mat, codes, _ = _inputs(seed=2)
    cfg = ElasticityConfig(budget_g_per_epoch=1.5, **CFG)
    a = simulate_elastic_jax(demand, (region_mat, codes), cfg, 300.0,
                             record=True)
    b = simulate_elastic_jax(demand, (region_mat, codes), cfg, 300.0,
                             record=False)
    assert b.levels.shape[0] == 0
    sa, sb = a.summary(), b.summary()
    for k in sa:
        assert sa[k] == pytest.approx(sb[k], rel=1e-12), k


def test_sweep_population_jax_with_elasticity_matches_fleet():
    from repro.carbon.intensity import TraceProvider
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.cluster.slices import paper_family
    from repro.core.policy import (CarbonAgnosticPolicy,
                                   CarbonContainerPolicy)
    from repro.core.simulator import SimConfig, sweep_population
    from repro.traffic import TrafficConfig, UserPopulation
    from repro.traffic.autoscale import ReplicaConfig
    from repro.workload.azure_like import sample_population

    fam = paper_family()
    traces = [t.util for t in sample_population(6, days=1, seed=5)]
    provs = [TraceProvider.for_region(r, hours=24, seed=1)
             for r in ("PL", "NL", "CAISO")]
    pols = {"cc_energy": lambda: CarbonContainerPolicy("energy"),
            "carbon_agnostic": CarbonAgnosticPolicy}
    cfgb = SimConfig(target_rate=0.0)
    ec = ElasticityConfig(k_levels=4, unit_capacity=0.3,
                          budget_g_per_epoch=100.0, forecast="forecast",
                          shape_budget=True)
    tc = TrafficConfig(
        population=UserPopulation(n_users=100_000, n_regions=3, seed=3),
        replicas=ReplicaConfig(max_replicas=8, max_step=2))
    for traffic in (None, tc):
        mk = lambda: PlacementEngine(
            fam, provs, config=PlacementConfig(capacity=4, min_dwell=4))
        rows_f = sweep_population(pols, fam, traces, None, [30.0, 60.0],
                                  cfgb, backend="fleet", placement=mk(),
                                  traffic=traffic, elasticity=ec)
        rows_j = sweep_population(pols, fam, traces, None, [30.0, 60.0],
                                  cfgb, backend="jax", placement=mk(),
                                  traffic=traffic, elasticity=ec)
        assert len(rows_f) == len(rows_j) == 4
        for a, b in zip(rows_f, rows_j):
            assert a["policy"] == b["policy"]
            # level-epoch totals are integer counts: exact on both paths
            assert a["elastic_level_epochs"] == b["elastic_level_epochs"]
            assert a["elastic_cap_violations"] \
                == b["elastic_cap_violations"] == 0
            for k in ("carbon_rate_mean", "throttle_mean",
                      "migrations_mean", "elastic_served_work",
                      "elastic_emissions_g", "elastic_served_frac"):
                scale = max(abs(a[k]), 1.0)
                assert abs(a[k] - b[k]) <= TOL * scale, k


def test_shaped_budget_levels_exact_across_backends():
    # budget shaping swaps the scalar cap for a per-epoch series; the
    # series is precomputed host-side from the same signal on both
    # backends, so level counts stay bit-equal, not merely close
    demand, region_mat, codes, dense = _inputs(T=72, N=20, seed=4)
    for mode in ("oracle", "persistence", "forecast"):
        cfg = ElasticityConfig(budget_g_per_epoch=2.0, forecast=mode,
                               shape_budget=True, **CFG)
        a = simulate_elastic(demand, dense, cfg, 3600.0)
        b = simulate_elastic_jax(demand, (region_mat, codes), cfg, 3600.0,
                                 record=True)
        np.testing.assert_array_equal(a.levels, b.levels)
        assert a.cap_violations == b.cap_violations == 0


def test_shape_validation():
    demand, region_mat, codes, _ = _inputs()
    cfg = ElasticityConfig(**CFG)
    with pytest.raises(ValueError):
        simulate_elastic_jax(demand[0], region_mat, cfg)
    with pytest.raises(ValueError):
        simulate_elastic_jax(demand, (region_mat[:10], codes), cfg)
    with pytest.raises(ValueError):
        simulate_elastic_jax(demand, np.zeros((4, 4)), cfg)


@pytest.mark.parametrize("layout", ["dense", "indexed"])
def test_budgeted_scan_has_no_gather_or_scatter(layout):
    # the greedy maps its order back to containers by sorting; a TPU
    # runs gathers and scatters over the (N·K,) levels one element at a
    # time, so neither may come back into the program
    demand, region_mat, codes, dense = _inputs()
    carbon = dense if layout == "dense" else (region_mat, codes)
    cfg = ElasticityConfig(budget_g_per_epoch=2.0, **CFG)
    xs, T, n, R = _prepare(demand, carbon, cfg, 300.0, None, None)
    with jax.enable_x64(True):
        text = _build_scan(cfg, 300.0, n, R, False).lower(xs).as_text()
    assert text.count("stablehlo.sort") == 2
    assert "gather" not in text
    assert "scatter" not in text
