"""Spans and counters of the placed JAX sweep path.

Spans are `jax.profiler.TraceAnnotation`s: under the profiler they land in
the same trace as the device's programs, on the same clock, so each idle
gap of the device lies inside a named host span. With the profiler off a
span costs about a microsecond. The names are exact (readers match them by
equality):

    sweep                    `fleet_jax.sweep_population_jax`, the root;
                             metadata ``sweep=<n>``, the counters' number
      sweep.prepare          `fleet._prepare_sweep_inputs`, holds `plan`
        plan                 `placement_jax.plan_jax`
          plan.prepare       host inputs of the plan
          plan.h2d           its arguments pushed to the device
          plan.wait          the plan's program, dispatched and awaited
          plan.d2h           its carry and rounds pulled, the
                             `PlacementPlan` built (the assignments stay
                             on the device)
        sweep.faults         the observed carbon feed (`observe_intensity`)
                             and the power-meter gaps, under a fault plan
      sweep.traffic          `fleet._prepare_traffic`, its row readings and
                             the demand factor of each container's region
      sweep.energy           `fleet._prepare_energy`, its row readings and
                             the cap and intensities handed on
      sweep.elastic_budget   `fleet._elastic_budget_series`
      sweep.elastic          the elasticity layer, its served demand and
                             row readings
        elastic.prepare      `elasticity_jax.simulate_elastic_jax`: the
                             demand and carbon forecasts and the budgets
        elastic.h2d          its inputs pushed to the device
        elastic.wait         the elasticity scan, dispatched and awaited
        elastic.d2h          the served demand, backlog and totals pulled
      fleet.prepare          host inputs of `FleetSimulatorJax.run`
      fleet.h2d              its host arguments pushed, per shard (the
                             planner's device arrays are taken up)
      fleet.wait             the fleet scans awaited
      fleet.d2h              the scans' carries pulled and joined
      fleet.result           the `FleetResult` built
      sweep.aggregate        `fleet._aggregate_sweep_rows`

Counters are kept per sweep, in memory, and start from zero when `sweep`
opens; `last_sweep()` returns a copy:

    h2d_bytes, d2h_bytes     bytes the h2d and d2h spans move
    handoff_bytes            bytes the fleet scan took from the planner's
                             device arrays (codes, demand) in place of a
                             push from the host
    admission_rounds         preference rounds of the plan, over its epochs
    elastic_level_epochs     resource levels the elasticity scan allocated,
                             summed over its epochs and containers
"""
from __future__ import annotations

import contextlib

import jax

COUNTERS = ("h2d_bytes", "d2h_bytes", "handoff_bytes", "admission_rounds",
            "elastic_level_epochs")

span = jax.profiler.TraceAnnotation

_counts = dict.fromkeys(COUNTERS + ("sweep",), 0)
_seq = 0


@contextlib.contextmanager
def sweep():
    """The root span of one sweep; opening it resets the counters."""
    global _seq
    _seq += 1
    _counts.clear()
    _counts.update(dict.fromkeys(COUNTERS, 0), sweep=_seq)
    with span("sweep", sweep=_seq):
        yield


def count(name: str, n) -> None:
    _counts[name] += int(n)


def nbytes(tree) -> int:
    """Bytes of the arrays in `tree`."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def last_sweep() -> dict:
    """The counters of the last sweep opened (or of work done outside any
    sweep since), as a copy."""
    return dict(_counts)
