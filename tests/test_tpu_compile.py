"""Compile guard: the DVV kernels of the served path compile for a v5e.

Interpret mode (the CPU test path) cannot see what the TPU's kernel
compiler refuses: unsupported ops, misaligned tiles, VMEM overuse.  These
tests compile ``dvv_sync_mask_pallas`` and the read sweep for a described,
not attached, ``v5e:2x2`` chip at shape buckets the store produces (K up to
256, the bucket of a quorum read over two replicas that each hold Riak's
100-sibling maximum; N up to 65,536), and check that the kernel is a
``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.dvv_ops.dvv_ops import dvv_read_sweep_pallas, \
    dvv_sync_mask_pallas

SHAPES = [(8, 2, 8), (1024, 4, 8), (4096, 8, 8), (4096, 16, 8),
          (65536, 8, 8), (8, 32, 8), (8, 64, 8), (8, 128, 8), (8, 256, 8),
          (32, 128, 8)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
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
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", [dvv_sync_mask_pallas,
                                    dvv_read_sweep_pallas],
                         ids=["sync_mask", "read_sweep"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_dvv_kernel_compiles_for_v5e(kernel, shape, one_chip,
                                     no_persistent_cache):
    N, K, R = shape

    def spec(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = kernel.lower(spec((N, K, R)), spec((N, K)), spec((N, K)),
                            spec((N, K), jnp.bool_),
                            interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
