"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
float64 value inside a Mosaic kernel, an int64 block index, an f64 LU solve.
These tests compile the allocator's main-path programs at real widths for
one chip of a described ``v5e:2x2`` topology, under the float64 that
``import repro.core`` turns on. Nothing runs; a passing compile is not a chip
run. The topology is described inside a fixture, so only the worker that runs
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (turns on x64, as the real path runs)
from repro.core import engine
from repro.kernels.crms_grid import crms_grid_eval

F64 = jnp.float64
GRID_KW = dict(caps_cpu=30.0, power_span=150.0, alpha=1.4, beta=0.2)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache off around these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, *shape, dtype=F64):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed(sharding, *lead):
    fields = ("lam", "xbar", "r_min", "r_max", "cpu_min", "cpu_max")
    packed = {k: _spec(sharding, *lead) for k in fields}
    packed["kappa"] = _spec(sharding, *lead, 3)
    return packed


@pytest.mark.parametrize("reduce", ["sum", "per_app"])
@pytest.mark.parametrize("M,B", [(4, 72), (64, 288)])
def test_crms_grid_compiles_under_x64(one_chip, M, B, reduce):
    assert jax.config.jax_enable_x64
    s = _spec
    fn = jax.jit(lambda *a: crms_grid_eval(*a, reduce=reduce, **GRID_KW))
    compiled = fn.lower(
        s(one_chip, M, 3), s(one_chip, M), s(one_chip, M),
        s(one_chip, B, M), s(one_chip, B, M), s(one_chip, B, M),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_p1_structured_batch_compiles(one_chip):
    """The refinement's P1 solve: 2M = 128 moves of a 64-tenant node."""
    M, B = 64, 128
    n_outer, n_inner = engine.P1_PROFILES["refine"]
    scalar = _spec(one_chip)
    compiled = engine._ip_solve_batched.lower(
        _spec(one_chip, B, 2 * M), _packed(one_chip, M), _spec(one_chip, B, M),
        scalar, scalar, scalar, 1.4, 0.2, n_outer=n_outer, n_inner=n_inner,
    ).compile()
    assert compiled.as_text()


def test_p1_single_row_compiles_at_the_derived_width(one_chip):
    """The re-plan's single-row P1 of a paper node, at the Erlang width
    p1_solve_batch derives for counts <= 16."""
    M = 4
    n_outer, n_inner = engine.P1_PROFILES["reference"]
    scalar = _spec(one_chip)
    compiled = engine._ip_solve_batched.lower(
        _spec(one_chip, 1, 2 * M), _packed(one_chip, M), _spec(one_chip, 1, M),
        scalar, scalar, scalar, 1.4, 0.2, n_outer=n_outer, n_inner=n_inner,
        width=engine.P1_MIN_WIDTH,
    ).compile()
    assert compiled.as_text()


def test_ip_solve_rows_compiles(one_chip):
    """The region planner's row solve: 1024 nodes × 16 app slots."""
    N, M = 1024, 16
    packed = _packed(one_chip, N, M)
    packed["mask"] = _spec(one_chip, N, M)
    n_outer, n_inner = engine.P1_PROFILES["fleet"]
    compiled = engine._ip_solve_rows.lower(
        _spec(one_chip, N, 2 * M), packed, _spec(one_chip, N, M),
        _spec(one_chip, N), _spec(one_chip, N), _spec(one_chip), 1.4, 0.2,
        n_outer=n_outer, n_inner=n_inner, solver="structured", t0=1.0, width=32,
    ).compile()
    assert compiled.as_text()
