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

It also compiles the client pool's cohort gather at the FEMNIST cells' pool
(3,550 clients x 400 examples x 784 floats) at the pool's device shape
(``sim/pool.py::device_shape``): the chip's default format is then
row-major, and the gather holds no pool-sized copy, on one chip and sharded
over four.

The topology is described inside a module fixture, never at import: only
the process that runs these tests loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.compression import MATERIAL_ARITY
from repro.kernels import ops
from repro.sim import pool

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


# the FEMNIST cells' pool: (clients, max_examples, 784) f32 images, (clients,
# max_examples) s32 labels; a 32-client cohort of 8 steps x 20 samples
POOL_ROWS, POOL_EXAMPLES, POOL_F = 3550, 400, 784
COHORT = (32, 8, 20)
SHAPES_X_Y = (("x", (POOL_F,)), ("y", ()))
# collectives that would move pool rows between chips; the sharded gather's
# reductions (all-reduce, reduce-scatter) must be cohort-sized
POOL_MOVES = re.compile(r"all-gather|all-to-all|collective-permute")
REDUCTION = re.compile(r"= \w+\[(\d+)[,\]]\S* (?:all-reduce|reduce-scatter)\(")
TEMP_LIMIT = 64 << 20


def _pool_copies(text, rows):
    """The ops that copy a pool-shaped operand (``rows`` leading rows)."""
    return re.findall(rf"= \w+\[{rows},\d+(?:,\d+)?\]\S* copy\(", text)


def _pool_specs(sharding, rows, padded):
    shapes = {"x": ((rows, POOL_EXAMPLES, POOL_F), jnp.float32),
              "y": ((rows, POOL_EXAMPLES), jnp.int32)}
    return {k: jax.ShapeDtypeStruct(pool.device_shape(s, dt) if padded else s, dt,
                                    sharding=sharding)
            for k, (s, dt) in shapes.items()}


def _index_specs(sharding, n_index):
    n, r, b = COHORT
    spec = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=sharding)  # noqa: E731
    return ((spec((n,), jnp.int32),) * n_index
            + (spec((n, r, b), jnp.int32), spec((n, r), jnp.float32)))


POOL_CASES = {
    # (program, padded pool): the program ClientPool.gather runs, a jitted
    # gather_batch (the scan body's gather), and the pool at its unpadded
    # shape, whose default format is client-minor
    "gather_jit": (lambda: pool._gather_jit, True),
    "gather_batch": (lambda: jax.jit(pool.gather_batch, static_argnums=1), True),
    "gather_jit-unpadded": (lambda: pool._gather_jit, False),
}


@pytest.mark.parametrize("case", [*POOL_CASES, "sharded-gather-4chips"])
def test_pool_compiles_for_v5e(topo, no_persistent_cache, case):
    """At ``device_shape`` the pool's default format on the chip is
    row-major and the gather reads it in place: no copy of a pool-shaped
    operand and under 64 MB of temp.  At the unpadded shape the default
    format is client-minor and the copy is there, so the check tells the
    two apart.  The sharded gather on four chips reads each shard's rows
    in place too, and its only collectives are cohort-sized reductions."""
    if case == "sharded-gather-4chips":
        mesh = Mesh(np.array(topo.devices[:4]), ("data",))
        rows = POOL_ROWS + (-POOL_ROWS) % 4
        buffers = _pool_specs(NamedSharding(mesh, P("data")), rows, True)
        gather = pool._sharded_gather(mesh, "data", SHAPES_X_Y)
        compiled = gather.lower(buffers, *_index_specs(NamedSharding(mesh, P()), 2)).compile()
        text = compiled.as_text()
        lead = [int(d) for d in REDUCTION.findall(text)]
        assert not _pool_copies(text, rows // 4) and not POOL_MOVES.search(text)
        assert lead and max(lead) <= COHORT[0], lead
        assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
        return
    program, padded = POOL_CASES[case]
    one = SingleDeviceSharding(topo.devices[0])
    compiled = program().lower(_pool_specs(one, POOL_ROWS, padded), SHAPES_X_Y,
                               *_index_specs(one, 1)).compile()
    copies = _pool_copies(compiled.as_text(), POOL_ROWS)
    temp = compiled.memory_analysis().temp_size_in_bytes
    formats = {k: f.layout.major_to_minor for k, f in compiled.input_formats[0][0].items()}
    row_major = formats == {"x": (0, 1, 2), "y": (0, 1)}
    if padded:
        assert row_major and not copies and temp < TEMP_LIMIT, (formats, copies, temp)
    else:
        assert formats["x"] != (0, 1, 2) and copies and temp > TEMP_LIMIT, (formats, copies, temp)
