"""Fused norm-&-aggregate — both OCS reductions from ONE HBM tile stream.

The OCS critical path touches the client-major update matrix twice: once to
emit the per-client squared norms (paper Alg. 1 line 3: ``u_i = ||w_i U_i||``,
the input to Eq. 7's probabilities) and once to contract Eq. 2's masked
aggregate ``G = sum_i scale_i * U_i``.  Run separately
(client_norm.client_sqnorms_pallas + masked_aggregate.masked_scale_aggregate_pallas)
that is two full passes over HBM.  This kernel emits BOTH outputs from a
single ``(clients, chunk)`` tile stream: each grid step reads one tile,
row-reduces the squares into the resident squared-norm accumulator AND
contracts the scale row against the tile into its ``chunk`` slice of the
aggregate — one HBM read per update element,
total, for the whole post-plan reduction work of a round.

The single-pass scan engine (fl/engine.py) is the consumer: post-plan, each
cached (or spill-recomputed) group matrix streams through here once, yielding
the group's aggregate partial plus its squared norms for free from the same
tiles — the norms re-emitted on the aggregate pass are a zero-cost cache
integrity signal (they must equal pass 1's, which
tests/test_norm_aggregate.py gates).

Grid: (num_chunks,).  Blocks: the ``(1, C)`` scale row and the ``(C, 1)``
squared-norm column map to the same block every step (both stay resident in
VMEM); updates stream as ``(C, CHUNK)`` tiles; the aggregate output block
``(1, CHUNK)`` at chunk ``i`` is touched by exactly one grid step, so only the
norm output needs cross-step accumulation.  Every operand is 2-D, the form
the TPU compiler accepts for the contraction.

In-stream compression (``compress_norm_scale_aggregate_pallas``): the same
tile stream additionally applies the unbiased compressor — the pure
elementwise ``core.compression.apply_compression_flat`` map over the tile and
its precomputed per-tile key material (the per-client-subkey PRNG draws,
streamed as extra ``(C, CHUNK)`` operands) — BEFORE the two reductions, so
the compressed update ``C(U_i)`` never materialises in HBM at all: one read
of the raw update (plus its material) replaces the old
compress-write / norm-read / aggregate-read triple pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compression import MATERIAL_ARITY, apply_compression_flat
from repro.kernels.masked_aggregate import scale_contract


def _norm_agg_kernel(s_ref, x_ref, sq_ref, o_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[...].astype(jnp.float32)
    sq_ref[...] += jnp.sum(x * x, axis=-1, keepdims=True)
    o_ref[...] = scale_contract(s_ref[...], x)


def norm_scale_aggregate_pallas(
    updates: jax.Array, scale: jax.Array, chunk: int = 4096, interpret: bool = False
):
    """updates: (clients, D), scale: (1, clients) ->
    ((clients, 1) f32 squared norms, (1, D) f32 aggregate), one HBM pass.

    D is padded to a multiple of ``chunk`` by the wrapper in ops.py (zero
    padding changes neither output), which also reshapes the vectors.
    """
    c, d = updates.shape
    assert scale.shape == (1, c), (scale.shape, c)
    assert d % chunk == 0, (d, chunk)
    grid = (d // chunk,)
    return pl.pallas_call(
        _norm_agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((c, chunk), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, chunk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(scale, updates)


def _make_compress_norm_agg_kernel(kind: str, param: float, n_mats: int,
                                   in_dtype):
    """Kernel body closure: compress the tile in-stream, then both reductions.

    ``kind``/``param``/``n_mats`` are static per pallas_call; the compressed
    tile is cast through the transport dtype (``in_dtype``) so its values are
    bitwise what the jnp path materialises before its own f32 reductions.
    """

    def kernel(*refs):
        s_ref, x_ref = refs[0], refs[1]
        mat_refs = refs[2:2 + n_mats]
        sq_ref, o_ref = refs[2 + n_mats], refs[3 + n_mats]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            sq_ref[...] = jnp.zeros_like(sq_ref)

        x = x_ref[...].astype(jnp.float32)
        xc = apply_compression_flat(x, kind, param, *[m[...] for m in mat_refs])
        xc = xc.astype(in_dtype).astype(jnp.float32)
        sq_ref[...] += jnp.sum(xc * xc, axis=-1, keepdims=True)
        o_ref[...] = scale_contract(s_ref[...], xc)

    return kernel


def compress_norm_scale_aggregate_pallas(
    updates: jax.Array, scale: jax.Array, mats: tuple, kind: str, param: float,
    chunk: int = 4096, interpret: bool = False,
):
    """updates (clients, D) + material -> ((clients, 1) sq norms of C(U),
    (1, D) aggregate of C(U)) — compression fused into the same tile stream.

    ``scale`` is the ``(1, clients)`` row, as in
    :func:`norm_scale_aggregate_pallas`.

    ``mats`` is the tuple of ``(clients, D)`` f32 material matrices
    (``core.compression.compression_material`` flattened client-major, one
    per ``MATERIAL_ARITY[kind]``); each streams tile-for-tile alongside the
    raw updates, the elementwise compressor runs in VMEM, and both OCS
    reductions consume the compressed tile — one HBM read of each update, no
    compressed intermediate.  ``kind='none'`` degenerates to
    :func:`norm_scale_aggregate_pallas` exactly.  D is padded to a ``chunk``
    multiple by the wrapper in ops.py (zero values + zero material compress
    to zero for every kind, so padding changes neither output).
    """
    c, d = updates.shape
    assert scale.shape == (1, c), (scale.shape, c)
    assert d % chunk == 0, (d, chunk)
    assert len(mats) == MATERIAL_ARITY[kind], (kind, len(mats))
    for m in mats:
        assert m.shape == (c, d), (m.shape, (c, d))
    grid = (d // chunk,)
    kernel = _make_compress_norm_agg_kernel(kind, param, len(mats),
                                            updates.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((c, chunk), lambda i: (0, i)),
        ] + [pl.BlockSpec((c, chunk), lambda i: (0, i)) for _ in mats],
        out_specs=[
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, chunk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(scale, updates, *mats)
