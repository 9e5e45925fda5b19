"""Spans and counters of the placed JAX sweep path (`repro.obs`).

A small placed sweep runs on the CPU under the profiler; its host spans
are read back from the trace. The counters are checked against the
bytes of the arrays each transfer moves, and the admission-round counter
against the Pallas kernel (interpret mode) and a fleet worked by hand.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.carbon.intensity import TraceProvider
from repro.cluster.placement import PlacementConfig, PlacementEngine
from repro.cluster.placement_jax import plan_jax
from repro.cluster.slices import paper_family
from repro.core.policy import CarbonContainerPolicy
from repro.core.simulator import SimConfig, sweep_population
from repro.workload.azure_like import sample_population

REGIONS = ("PL", "NL", "CAISO")
N_TR, TARGETS = 12, (30.0, 60.0)

# the spans every placed sweep opens, one each (one policy, one device)
PLACED = ("sweep", "sweep.prepare", "plan", "plan.prepare", "plan.h2d",
          "plan.wait", "plan.d2h", "fleet.prepare", "fleet.h2d",
          "fleet.wait", "fleet.d2h", "fleet.result", "sweep.aggregate")
# spans that lie directly under `sweep` and, with `plan`'s children
# under `plan`, leave no host work of the placed path unnamed
SWEEP_PARTS = ("sweep.prepare", "fleet.prepare", "fleet.h2d", "fleet.wait",
               "fleet.d2h", "fleet.result", "sweep.aggregate")
PLAN_PARTS = ("plan.prepare", "plan.h2d", "plan.wait", "plan.d2h")


def _engine(n):
    provs = [TraceProvider.for_region(r, hours=24, seed=1) for r in REGIONS]
    # capacity binds, so every epoch runs admission rounds
    return PlacementEngine(paper_family(), provs, region_names=REGIONS,
                           config=PlacementConfig(capacity=n // 2,
                                                  min_dwell=4))


def _sweep():
    traces = [t.util for t in sample_population(N_TR, days=1, seed=5)]
    return sweep_population(
        {"cc": lambda: CarbonContainerPolicy("energy")}, paper_family(),
        traces, None, list(TARGETS), SimConfig(target_rate=0.0),
        backend="jax", placement=_engine(N_TR))


def _host_events(tdir):
    path = sorted(glob.glob(f"{tdir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats) if e.name == "sweep" else {})
                           for e in line.events)
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Rows of one sweep with the profiler off, then two sweeps under the
    profiler: (rows off, rows on, host events, counters of the last)."""
    off = _sweep()
    tdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(tdir):
        on = [_sweep(), _sweep()]
    return off, on, _host_events(tdir), obs.last_sweep()


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(e, outer):
    return outer[1] <= e[1] and e[2] <= outer[2]


def test_every_placed_span_once_per_sweep_inside_it(traced):
    _, _, events, counts = traced
    roots = sorted(_named(events, "sweep"), key=lambda e: e[1])
    assert len(roots) == 2
    # the root carries the sweep's number, the one its counters carry
    assert [int(r[3]["sweep"]) for r in roots] == [counts["sweep"] - 1,
                                                   counts["sweep"]]
    for root in roots:
        for name in PLACED[1:]:
            inside = [e for e in _named(events, name) if _inside(e, root)]
            assert len(inside) == 1, name
    for e in _named(events, "plan"):
        prep = [p for p in _named(events, "sweep.prepare") if _inside(e, p)]
        assert len(prep) == 1
    for name in PLAN_PARTS:
        for e in _named(events, name):
            assert any(_inside(e, p) for p in _named(events, "plan")), name
    # off this path: no traffic, energy, elasticity or fault spans
    for name in ("sweep.traffic", "sweep.energy", "sweep.elastic_budget",
                 "sweep.faults", "sweep.elastic", "elastic.prepare",
                 "elastic.h2d", "elastic.wait", "elastic.d2h"):
        assert _named(events, name) == []


@pytest.mark.parametrize("parent,parts", [("sweep", SWEEP_PARTS),
                                          ("plan", PLAN_PARTS)])
def test_parts_do_not_overlap_and_cover_their_parent(traced, parent, parts):
    _, _, events, _ = traced
    for p in _named(events, parent):
        iv = sorted((e[1], e[2]) for n in parts for e in _named(events, n)
                    if _inside(e, p))
        assert all(a[1] <= b[0] for a, b in zip(iv, iv[1:]))
        covered = sum(b - a for a, b in iv)
        # what lies between the parts is dispatch and object set-up
        assert covered >= 0.8 * (p[2] - p[1]), (parent, covered, p)


def test_rows_with_the_profiler_on_equal_rows_with_it_off(traced):
    off, on, _, _ = traced
    assert on[0] == off and on[1] == off


def test_transfer_bytes_are_the_arrays_moved(traced):
    _, _, _, counts = traced
    T, R, n, N = 288, len(REGIONS), N_TR, N_TR * len(TARGETS)
    S = len(paper_family().tables().names)
    f8, i4 = 8, 4
    plan_up = (T * R * f8 + T * n * f8        # region carbon, demand
               + n * i4 + R * i4 + R * i4      # initial region, occ, cap
               + 2 * n * f8)                   # cost0, mig_s
    plan_down = (3 * n * i4 + 2 * n * f8 + R * i4   # final carry
                 + T * i4)                          # rounds
    fleet_up = (T * R * f8                    # region carbon
                + 3 * N * f8)                 # targets, epsilon, state
    fleet_down = 4 * N * f8 + (S + 7) * N * i4     # acc, dyni
    assert counts["h2d_bytes"] == plan_up + fleet_up
    assert counts["d2h_bytes"] == plan_down + fleet_down
    # the plan's assignments and demand stay on the device for the scan
    assert counts["handoff_bytes"] == T * n * (f8 + i4)


def _plan_inputs(n, seed=13):
    provs = [TraceProvider.for_region(r, hours=24, seed=1) for r in REGIONS]
    traces = [t.util for t in sample_population(n, days=1, seed=seed)]
    eng = PlacementEngine(paper_family(), provs, region_names=REGIONS,
                          config=PlacementConfig(capacity=7 * n // 18,
                                                 min_dwell=4))
    return eng, np.stack(traces, axis=1)


def test_admission_rounds_agree_between_xla_and_pallas():
    eng, demand = _plan_inputs(18)
    plans = {}
    for impl in ("xla", "pallas"):
        with obs.sweep():
            plans[impl] = plan_jax(eng, demand, admission_impl=impl)
            counts = obs.last_sweep()
        rounds = plans[impl].admission_rounds
        assert rounds.shape == (demand.shape[0],)
        assert counts["admission_rounds"] == int(rounds.sum())
    x, p = plans["xla"].admission_rounds, plans["pallas"].admission_rounds
    np.testing.assert_array_equal(x, p)
    assert (x >= 1).all() and (x <= len(REGIONS)).all()
    # denials happen: some epoch needs more than one round
    assert x.max() > 1


def test_admission_rounds_of_a_two_region_fleet_worked_by_hand():
    """Four containers start in region A; B holds two. In epochs 0 and 1
    B is far cleaner. Epoch 0: all four want B, two are admitted, two
    denied; a second round finds the denied ones nothing else to want
    (A is where they are): 2 rounds. Epoch 1: the two moved ones sit out
    their dwell, the two denied still want the full B and are denied
    again: 2 rounds. Epochs 2 and 3: equal intensities, so no move saves
    anything and the first round wants nothing: 1 round each."""
    cmat = np.array([[500.0, 50.0], [500.0, 50.0],
                     [300.0, 300.0], [300.0, 300.0]])
    eng = PlacementEngine(paper_family(), cmat, region_names=("A", "B"),
                          config=PlacementConfig(capacity=(4, 2),
                                                 min_dwell=2))
    demand = np.full((4, 4), 0.5)
    initial = np.zeros(4, dtype=np.int64)
    for impl in ("xla", "pallas"):
        with obs.sweep():
            plan = plan_jax(eng, demand, state_gb=0.25, initial=initial,
                            admission_impl=impl)
            counts = obs.last_sweep()
        assert plan.admission_rounds.tolist() == [2, 2, 1, 1], impl
        assert plan.assign.tolist() == [[1, 1, 0, 0]] * 4, impl
        assert counts["admission_rounds"] == 6


def test_no_admission_no_rounds():
    eng, demand = _plan_inputs(6)
    eng.config = PlacementConfig(capacity=None, min_dwell=4)
    assert plan_jax(eng, demand).admission_rounds is None
    one = PlacementEngine(paper_family(), eng.regions[:1],
                          config=PlacementConfig(capacity=6))
    assert plan_jax(one, demand).admission_rounds is None


def test_counters_start_from_zero_with_each_sweep():
    with obs.sweep():
        obs.count("h2d_bytes", 5)
        first = obs.last_sweep()
    with obs.sweep():
        second = obs.last_sweep()
    assert first["h2d_bytes"] == 5
    assert second["sweep"] == first["sweep"] + 1
    assert {k: second[k] for k in obs.COUNTERS} == dict.fromkeys(
        obs.COUNTERS, 0)
    # a copy: changing it changes nothing kept
    second["h2d_bytes"] = 9
    assert obs.last_sweep()["h2d_bytes"] == 0


# --- the all-layer path: faults, elasticity ---------------------------

ELASTIC = ("elastic.prepare", "elastic.h2d", "elastic.wait", "elastic.d2h")


def _layered_sweep():
    from repro.core.elasticity import ElasticityConfig
    from repro.robustness import CarbonFeedFaults, FaultPlan
    traces = [t.util for t in sample_population(N_TR, days=1, seed=5)]
    return sweep_population(
        {"cc": lambda: CarbonContainerPolicy("energy")}, paper_family(),
        traces, None, list(TARGETS), SimConfig(target_rate=0.0),
        backend="jax", placement=_engine(N_TR),
        elasticity=ElasticityConfig(k_levels=2, unit_capacity=0.3,
                                    budget_g_per_epoch=2.5 * N_TR),
        faults=FaultPlan(carbon=CarbonFeedFaults(dropout_prob=0.2), seed=3))


@pytest.fixture(scope="module")
def traced_layers(tmp_path_factory):
    off = _layered_sweep()
    tdir = str(tmp_path_factory.mktemp("trace_layers"))
    with jax.profiler.trace(tdir):
        on = _layered_sweep()
    return off, on, _host_events(tdir)


def test_elastic_and_fault_spans_once_per_sweep_inside_it(traced_layers):
    _, _, events = traced_layers
    (root,) = _named(events, "sweep")
    for name in ELASTIC + ("sweep.faults",):
        assert len([e for e in _named(events, name) if _inside(e, root)]) \
            == 1, name
    (faults,) = _named(events, "sweep.faults")
    assert _inside(faults, _named(events, "sweep.prepare")[0])
    (layer,) = _named(events, "sweep.elastic")
    assert all(_inside(e, layer) for n in ELASTIC for e in _named(events, n))
    iv = sorted((e[1], e[2]) for n in ELASTIC for e in _named(events, n))
    assert all(a[1] <= b[0] for a, b in zip(iv, iv[1:]))
    # the scan runs after the budget is made and before the fleet scan
    (budget,) = _named(events, "sweep.elastic_budget")
    (fleet,) = _named(events, "fleet.prepare")
    assert budget[2] <= iv[0][0] and iv[-1][1] <= fleet[1]


def test_rows_with_the_profiler_on_equal_rows_with_it_off_all_layers(
        traced_layers):
    off, on, _ = traced_layers
    assert on == off


def test_elastic_counters_are_the_arrays_moved():
    from repro.core.elasticity import ElasticityConfig
    from repro.core.elasticity_jax import simulate_elastic_jax
    T, n, R = 288, 7, len(REGIONS)
    rng = np.random.default_rng(2)
    demand = rng.uniform(0.0, 1.0, (T, n))
    region = rng.uniform(100.0, 700.0, (T, R))
    codes = rng.integers(0, R, (T, n))
    cfg = ElasticityConfig(k_levels=3, unit_capacity=0.3,
                           budget_g_per_epoch=4.0)
    with obs.sweep():
        res = simulate_elastic_jax(demand, (region, codes), cfg)
        counts = obs.last_sweep()
    f8, i4 = 8, 4
    up = (2 * T * n * f8              # demand, its forecast
          + T * f8                    # budgets
          + T * n * i4                # region codes
          + 2 * T * R * f8)           # region carbon, its forecast
    down = T * n * f8 + n * f8 + 5 * f8     # served, backlog, totals
    assert counts["h2d_bytes"] == up
    assert counts["d2h_bytes"] == down
    assert counts["elastic_level_epochs"] == res.level_epochs
    assert 0 < res.level_epochs == res.summary()["elastic_level_epochs"]


def _numpy_cut_epochs(demand, carbon, cfg, monkeypatch):
    """Epochs in which the NumPy backend allocated fewer levels than the
    containers desired (the budget left an optional level out)."""
    import dataclasses

    from repro.core import elasticity
    free = dataclasses.replace(cfg, budget_g_per_epoch=None,
                               shape_budget=False)
    alloc, cuts = elasticity.allocate_epoch, []

    def counted(want, chat, prev, cfg, dt, budget_g=None):
        got, lo = alloc(want, chat, prev, cfg, dt, budget_g=budget_g)
        desired, _ = alloc(want, chat, prev, free, dt)
        cuts.append(bool(got.sum() < desired.sum()))
        return got, lo

    monkeypatch.setattr(elasticity, "allocate_epoch", counted)
    elasticity.simulate_elastic(demand, carbon, cfg)
    return sum(cuts)


@pytest.mark.parametrize("budget,binds", [(None, "never"), (4.0, "always"),
                                          (80.0, "sometimes"),
                                          (400.0, "never")])
def test_elastic_cut_epochs_are_the_numpy_backends_cuts(budget, binds,
                                                        monkeypatch):
    from repro.core.elasticity import ElasticityConfig
    from repro.core.elasticity_jax import simulate_elastic_jax
    T, n = 96, 7
    rng = np.random.default_rng(3)
    demand = rng.uniform(0.0, 1.0, (T, n))
    carbon = rng.uniform(100.0, 700.0, (T, n))
    cfg = ElasticityConfig(k_levels=3, unit_capacity=0.3,
                           budget_g_per_epoch=budget, forecast="oracle")
    with obs.sweep():
        simulate_elastic_jax(demand, carbon, cfg)
        got = obs.last_sweep()["elastic_cut_epochs"]
    assert got == _numpy_cut_epochs(demand, carbon, cfg, monkeypatch)
    assert {"never": got == 0, "always": got == T,
            "sometimes": 0 < got < T}[binds], got
