"""Build one cell's sweep on the program under test from its configuration
file and generated inputs, and keep the region plan the sweep computes.

The timed path is `SweepSpec.run()` on the JAX backend: the placed sweep,
with the keywords each layer the mix turns on sets (`bench.layers`). The
mix's reference (`bench/ref/`) reads the same configuration file on its
own.
"""
from __future__ import annotations

from bench import cells
from bench.ref.placed import capacity


def family(cfg: dict):
    """The configuration's slice family as the program's classes."""
    from repro.cluster.slices import Slice, SliceFamily
    from repro.power.model import LinearPowerModel
    s = cfg["slices"]
    return SliceFamily(
        [Slice(name, m, LinearPowerModel(s["base_w"] * m, s["peak_w"] * m),
               state_bw_gbps=s["state_bw_gbps"])
         for name, m in zip(s["names"], s["multiples"])],
        baseline_idx=s["baseline"])


def program_sweep(cfg: dict, mix: dict, inputs: dict):
    """The timed path: a `SweepSpec` on the JAX backend over every target,
    with each of the mix's layers. Refuses a layer its reference does not
    model, a layer with no file, and a keyword set twice."""
    from repro.cluster.migration import MigrationCostModel
    from repro.cluster.placement import PlacementConfig, PlacementEngine
    from repro.core.policy import CarbonContainerPolicy
    from repro.core.simulator import SimConfig
    from repro.core.spec import SweepSpec
    cells.reference(mix)
    fam = family(cfg)
    sim, pol, p = cfg["sim"], cfg["policy"], cfg["placement"]
    if pol["name"] != "carbon_containers":
        raise ValueError(f"unknown policy {pol['name']!r}")
    engine = PlacementEngine(
        fam, inputs["regions"], interval_s=sim["interval_s"],
        migration=MigrationCostModel(**cfg["migration"]),
        config=PlacementConfig(capacity=capacity(cfg), **p),
        region_names=tuple(cfg["regions"]))
    kw = dict(
        backend="jax", family=fam, traces=inputs["traces"],
        targets=list(inputs["targets"]), placement=engine,
        policies={pol["name"]: lambda: CarbonContainerPolicy(
            variant=pol["variant"], min_dwell=pol["min_dwell"],
            idle_margin=pol["idle_margin"])},
        sim=SimConfig(target_rate=0.0, **sim))
    owner = dict.fromkeys(kw, "the placed sweep")
    for name, layer in cells.layers(mix).items():
        got = layer.program(cfg, mix["layers"][name], inputs)
        for k in got:
            if k in owner:
                raise ValueError(f"layer {name!r} sets {k!r}, which "
                                 f"{owner[k]} sets too")
            owner[k] = f"layer {name!r}"
        kw.update(got)
    return SweepSpec(**kw)


class PlanTap:
    """Keeps the last region plan the timed path computed.

    The JAX sweep looks `repro.cluster.placement_jax.plan_jax` up at every
    call; inside this context that name returns the same plan and keeps a
    reference to it, for the comparison after the window. Nothing is
    copied or computed."""

    def __enter__(self):
        import repro.cluster.placement_jax as pj
        self._mod, self._orig, self.last = pj, pj.plan_jax, None

        def tapped(*a, **k):
            self.last = self._orig(*a, **k)
            return self.last
        pj.plan_jax = tapped
        return self

    def __exit__(self, *exc):
        self._mod.plan_jax = self._orig
