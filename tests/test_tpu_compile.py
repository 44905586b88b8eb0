"""Compile rehearsal: the main-path kernels compile for a TPU v5e.

Interpret mode runs a kernel body in Python and accepts programs the TPU
compiler refuses (a contraction over a 1-D operand, tiles larger than the
scoped VMEM).  These tests compile each of the six kernels that form the
norms and Eq. 2's aggregate, through their ``kernels/ops.py`` wrappers, for
one chip of a described ``v5e:2x2`` topology — no chip is needed — and
check that the program holds the kernel (``tpu_custom_call``).  Shapes:

* 32 clients x the FEMNIST MLP's 58,430 parameters (the sim cells);
* 2 clients x mamba2-130m's 128,983,488 bf16 parameters (one scan group of
  ``launch/train.py --arch mamba2-130m --scan-group 2``);
* 512 clients with rand-k material, which needs the VMEM-sized tile
  (``ops.fit_chunk``).

The topology is described inside a module fixture, never at import: only
the process that runs these tests loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.compression import MATERIAL_ARITY
from repro.kernels import ops

FEMNIST_DIM = 58_430
MAMBA2_130M_DIM = 128_983_488

# name -> (clients, D, update dtype); the compress kernels stream rand-k
# material, one extra (clients, D) f32 operand
SHAPES = {
    "femnist-c32": (32, FEMNIST_DIM, jnp.float32),
    "mamba2-130m-c2": (2, MAMBA2_130M_DIM, jnp.bfloat16),
    "c512-randk": (512, FEMNIST_DIM, jnp.float32),
}
KIND, PARAM = "randk", 0.1

KERNELS = {
    "client_sqnorms": lambda u, s, m: ops.client_sqnorms(u, interpret=False),
    "masked_scale_aggregate": lambda u, s, m: ops.masked_scale_aggregate(
        u, s, interpret=False),
    "norm_scale_aggregate": lambda u, s, m: ops.norm_scale_aggregate(
        u, s, interpret=False),
    "compress_norm_scale_aggregate":
        lambda u, s, m: ops.compress_norm_scale_aggregate(
            u, s, m, KIND, PARAM, interpret=False),
    "shard_masked_aggregate": lambda u, s, m: ops.shard_masked_aggregate(
        u, s, interpret=False),
    "shard_compress_aggregate": lambda u, s, m: ops.shard_compress_aggregate(
        u, s, m, KIND, PARAM, interpret=False),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # keep the TPU compiler's logs off the filesystem
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    # a described-device compile is written to the persistent cache but can
    # never be read back without the chip: keep it out
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", list(KERNELS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_compiles_for_v5e(one_chip, no_persistent_cache, shape, kernel):
    """The wrapper's whole program compiles for one v5e chip and holds the
    Pallas kernel as a ``tpu_custom_call``."""
    c, d, dtype = SHAPES[shape]

    def spec(shape_, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape_, dt, sharding=one_chip)

    mats = tuple(spec((c, d)) for _ in range(MATERIAL_ARITY[KIND]))
    fn = KERNELS[kernel]
    compiled = jax.jit(lambda u, s, *m: fn(u, s, m)).lower(
        spec((c, d), dtype), spec((c,)), *mats
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
