"""The traversal kernels compile for a TPU v5e at the posdb-bfs deployment's
widths (2^20 vertices, a 2^15-slot frontier, the payload width).

Nothing runs: each kernel is lowered with ``interpret=False`` against a
described (not attached) v5e chip and compiled by the TPU compiler that is
installed with JAX, which refuses illegal block shapes, unsupported
primitives and VMEM overruns exactly as it would on the chip.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the worker running this file
loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.posdb_bfs import CONFIG
from repro.kernels.frontier_expand import expand_index_pallas
from repro.kernels.frontier_pull import pull_contrib_pallas
from repro.kernels.late_gather import late_gather_pallas
from repro.kernels.spmm_segment import spmm_segment_pallas

V = CONFIG.num_vertices                  # 2^20
E = V - 1                                # the tree's edge count
F = CONFIG.frontier_cap                  # 2^15


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: an
    entry written for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_tpu_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_frontier_expand_compiles(one_chip):
    i32 = lambda n: _shape(one_chip, (n,), jnp.int32)   # noqa: E731
    compiled = expand_index_pallas.lower(
        i32(F), i32(F), i32(F), E, capacity=F, interpret=False).compile()
    _assert_tpu_kernel(compiled)


def test_frontier_pull_compiles(one_chip):
    compiled = pull_contrib_pallas.lower(
        _shape(one_chip, (E,), jnp.int32), _shape(one_chip, (E,), jnp.int32),
        _shape(one_chip, (V,), jnp.bool_), _shape(one_chip, (V,), jnp.bool_),
        V, interpret=False).compile()
    _assert_tpu_kernel(compiled)


@pytest.mark.parametrize("width", [1, CONFIG.payload_cols, 128])
def test_late_gather_compiles(one_chip, width):
    compiled = late_gather_pallas.lower(
        _shape(one_chip, (E, width), jnp.float32),
        _shape(one_chip, (F,), jnp.int32), interpret=False).compile()
    _assert_tpu_kernel(compiled)


def test_spmm_segment_compiles(one_chip):
    compiled = spmm_segment_pallas.lower(
        _shape(one_chip, (V, 1), jnp.float32),
        _shape(one_chip, (E,), jnp.int32), _shape(one_chip, (E,), jnp.int32),
        _shape(one_chip, (E,), jnp.float32), V, interpret=False).compile()
    _assert_tpu_kernel(compiled)
