"""Mesh-native masked scale-&-aggregate — the per-shard half of Eq. 2.

Under the ``('pod','data')`` mesh every shard owns a contiguous block of
``k = n / axis_size`` clients.  The paper's communication pattern (Alg. 2:
scalars up, then ONE partial sum per shard) maps onto exactly two steps:

  1. a local fused contraction ``partial = sum_{i in shard} scale_i * U_i``
     over the shard's ``(k, D)`` client block — this kernel;
  2. a single cross-shard ``jax.lax.psum`` of the ``(D,)`` partials.

Nothing ever materialises the replicated ``(n, D)`` matrix that the
single-device path's ``ops.tree_masked_aggregate`` concatenates — the only
client-major buffer is the shard-local block that already lives on the shard.
The kernel is agnostic to what the rows hold: the shard_map round feeds it
raw updates or their compressed form ``C(U_i)`` (fl.compression) identically
— Eq. 2's contraction is the same either way, which is what keeps OCS
"orthogonal and compatible" with compression on the mesh path.  Since the
fused-compression PR the compressed form never materialises at all:
``sharded_compress_aggregate_pallas`` streams the RAW local block plus its
precomputed per-tile key material and runs the elementwise compressor inside
the same tile stream, emitting the shard's Eq. 2 partial AND the squared
norms of what each client actually sends from one HBM read.

Kernel schedule
---------------
``masked_aggregate.masked_scale_aggregate_pallas`` keeps the WHOLE client
axis resident in VMEM per tile (fine for the master-side matrices where
``c`` is the modest sampled-client count).  Here the local block can still be
large (``n / axis_size`` clients), so the grid gains a client-block axis:

  Grid: ``(num_chunks, num_client_blocks)`` — chunk-major so each output
  chunk is revisited across the *inner* client-block steps and the f32
  accumulator stays resident in VMEM.
  Blocks: updates ``(BC, CHUNK)`` tile; scale ``(1, BC)`` slice of the scale
  row; output ``(1, CHUNK)`` of the aggregate row at chunk ``i``, initialised
  at client-block 0 and accumulated in-place afterwards.

Each (scale-slice) x (tile) product is a ``(1, BC) @ (BC, CHUNK)`` matmul —
2-D operands, the form the TPU compiler accepts — and masking is folded
into the contraction (zero scale for unsampled clients), so the shard never
writes a scaled per-client
intermediate: one pass over the local block's HBM, one ``(1, CHUNK)`` VMEM
accumulator per output chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compression import MATERIAL_ARITY, apply_compression_flat
from repro.kernels.masked_aggregate import scale_contract


def _shard_agg_kernel(s_ref, x_ref, o_ref):
    j = pl.program_id(1)  # client-block step (inner grid axis)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += scale_contract(s_ref[...], x_ref[...])


def sharded_masked_aggregate_pallas(
    updates: jax.Array,
    scale: jax.Array,
    chunk: int = 4096,
    block_clients: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Local ``(k, D)`` client block, ``(1, k)`` scale row -> ``(1, D)`` f32
    partial aggregate.

    The shard-local half of Eq. 2: ``partial = sum_i scale_i * U_i`` over the
    clients this shard owns; callers ``psum`` the result over the client mesh
    axis to finish the estimator.  ``D`` must be a multiple of ``chunk`` and
    ``k`` a multiple of ``block_clients`` (the wrapper in ops.py pads both —
    zero-scale padding rows contribute nothing to the sum).
    """
    c, d = updates.shape
    assert scale.shape == (1, c), (scale.shape, c)
    assert d % chunk == 0, (d, chunk)
    assert c % block_clients == 0, (c, block_clients)
    grid = (d // chunk, c // block_clients)
    return pl.pallas_call(
        _shard_agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_clients), lambda i, j: (0, j)),
            pl.BlockSpec((block_clients, chunk), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, chunk), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(scale, updates)


def _make_shard_compress_kernel(kind: str, param: float, n_mats: int,
                                in_dtype):
    """Kernel body closure for the fused compress+norm+aggregate shard pass.

    Same 2-D chunk-major schedule as ``_shard_agg_kernel``; the tile is
    compressed in VMEM (elementwise ``apply_compression_flat`` over the raw
    tile + its material tiles) before feeding BOTH reductions — the squared
    norms of ``C(U_i)`` (block indexed by the client-block step ``j``,
    initialised on the first chunk and accumulated across chunks) and the
    Eq. 2 partial (indexed by chunk ``i``, accumulated across client blocks).
    """

    def kernel(*refs):
        s_ref, x_ref = refs[0], refs[1]
        mat_refs = refs[2:2 + n_mats]
        sq_ref, o_ref = refs[2 + n_mats], refs[3 + n_mats]
        i = pl.program_id(0)  # chunk step (outer grid axis)
        j = pl.program_id(1)  # client-block step (inner grid axis)

        @pl.when(j == 0)
        def _init_agg():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(i == 0)
        def _init_sq():
            sq_ref[...] = jnp.zeros_like(sq_ref)

        x = x_ref[...].astype(jnp.float32)
        xc = apply_compression_flat(x, kind, param, *[m[...] for m in mat_refs])
        xc = xc.astype(in_dtype).astype(jnp.float32)
        sq_ref[...] += jnp.sum(xc * xc, axis=-1, keepdims=True)
        o_ref[...] += scale_contract(s_ref[...], xc)

    return kernel


def sharded_compress_aggregate_pallas(
    updates: jax.Array,
    scale: jax.Array,
    mats: tuple,
    kind: str,
    param: float,
    chunk: int = 4096,
    block_clients: int = 128,
    interpret: bool = False,
):
    """Local ``(k, D)`` RAW client block + material, ``(1, k)`` scale row ->
    ``((k, 1) f32 squared norms of C(U), (1, D) f32 partial aggregate of
    C(U))``, compression fused.

    The shard-local half of Eq. 2 with the compressor run inside the same
    tile stream: ``partial = sum_i scale_i * C(U_i)`` over the clients this
    shard owns, plus the squared norms of what each client actually sends —
    one HBM read of the raw block, no compressed ``(k, D)`` intermediate.
    Callers ``psum`` the partial over the client mesh axis.  ``mats`` holds
    the ``MATERIAL_ARITY[kind]`` client-major ``(k, D)`` material matrices;
    the wrapper in ops.py pads both axes with zeros (zero scale + zero
    material rows/columns contribute nothing to either output).
    """
    c, d = updates.shape
    assert scale.shape == (1, c), (scale.shape, c)
    assert d % chunk == 0, (d, chunk)
    assert c % block_clients == 0, (c, block_clients)
    assert len(mats) == MATERIAL_ARITY[kind], (kind, len(mats))
    for m in mats:
        assert m.shape == (c, d), (m.shape, (c, d))
    grid = (d // chunk, c // block_clients)
    kernel = _make_shard_compress_kernel(kind, param, len(mats), updates.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_clients), lambda i, j: (0, j)),
            pl.BlockSpec((block_clients, chunk), lambda i, j: (j, i)),
        ] + [pl.BlockSpec((block_clients, chunk), lambda i, j: (j, i))
             for _ in mats],
        out_specs=[
            pl.BlockSpec((block_clients, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, chunk), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(scale, updates, *mats)
