"""Compile-only rehearsals of the main path for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which is installed with
jax, compiles for a chip that is described and not attached, and refuses
what the chip would refuse (unsupported types or layouts, too much fast
memory, a kernel Mosaic cannot lower). The shapes are the real ones of
the N=1M sweep (`benchmarks.figs.fleet_1m_spec`): the region plan and its
Pallas admission kernel at N = 100,000 traces and R = 3, and the indexed
fleet scan at N = 1,000,000 (100,000 columns tiled 10 times). Each
program compiles in seconds.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cluster import placement_jax
from repro.cluster.placement_pallas import admission_round
from repro.cluster.slices import paper_family
from repro.core import fleet_jax
from repro.core.policy import CarbonContainerPolicy

N_TRACES, N_TARGETS, T, R = 100_000, 10, 288, 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def S(one_chip):
    """Shape of one argument, placed on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture
def compile_for():
    """Compile a jitted function from shapes under enable_x64 (as the
    sweep runs) with the persistent compilation cache off (a described
    chip's entry cannot be read back). Returns the HLO text."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *args, **static):
        with jax.enable_x64(True):
            return fn.lower(*args, **static).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_admission_kernel_compiles_at_plan_width(compile_for, S):
    i32 = jnp.int32
    text = compile_for(
        jax.jit(lambda *a: admission_round(*a, interpret=False)),
        S((R, N_TRACES), i32), S((N_TRACES,), i32),
        S((N_TRACES,), jnp.bool_), S((N_TRACES,), i32), S((N_TRACES,), i32),
        S((R,), i32))
    assert text.count("tpu_custom_call") == 1


def test_plan_scan_compiles_with_the_kernel(compile_for, S):
    """The whole region plan as the sweep runs it on a TPU: capacity
    rounds through the compiled kernel, migration-failure retry state."""
    f64, i32 = jnp.float64, jnp.int32
    text = compile_for(
        placement_jax._plan_scan,
        S((T, R), f64), S((T, N_TRACES), f64), S((N_TRACES,), i32),
        S((R,), i32), S((R,), i32), S((N_TRACES,), f64),
        S((N_TRACES,), f64), S((T, N_TRACES), jnp.bool_),
        R=R, min_dwell=6, has_cap=True, base_b=100.0, span_b=100.0,
        mult_b=1.0, h_hr=1.0, hk=1.1, admission_impl="pallas",
        block_n=8192, interpret=False, has_faults=True, bb=1, bc=8)
    assert "tpu_custom_call" in text
    # the round keeps its name in the compiled program (and so in the
    # profiler's trace): one custom call, reached once per round
    calls = re.findall(r"^\s*%admission_round[\w.]* = .*custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text,
                       flags=re.M)
    assert len(calls) == 1


def test_indexed_fleet_scan_compiles_at_1m(compile_for, S):
    """The fleet scan over N = 1,000,000 containers in the indexed
    layout the placed sweep uses, with the observed feed and the
    telemetry-gap vector of the fault plan."""
    f64, i32 = jnp.float64, jnp.int32
    sim = fleet_jax.FleetSimulatorJax(paper_family())
    N = N_TRACES * N_TARGETS
    text = compile_for(
        fleet_jax._fleet_scan,
        S((T, N_TRACES), f64), (S((T, R), f64), S((T, N_TRACES), i32)),
        S((N,), f64), S((N,), f64), S((N,), f64), None, None, None,
        S((T, R), f64), S((T,), f64),
        spec=fleet_jax._policy_spec(CarbonContainerPolicy("energy")),
        srs=True, record=False, tabs=sim._tabs, dt=300.0,
        mig=sim._mig_spec(), cmode="indexed", n_rep=N_TARGETS, R=R)
    assert "while" in text                  # the epoch scan survived
