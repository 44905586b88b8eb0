"""Jitted public wrappers around the Pallas kernels.

Every wrapper follows one convention set: ``chunk`` is capped by
:func:`fit_chunk` so the streamed tiles fit the TPU's scoped VMEM, the
trailing model dim is padded to a multiple of it (padding rows/columns are
zeros, so reductions and contractions are unaffected), vectors travel to the
kernels as 2-D rows or columns (the TPU compiler refuses a contraction over
a 1-D operand), ``interpret`` defaults to backend detection (on a CPU
backend the kernels execute in ``interpret=True`` mode — the kernel body
runs in Python, validating the exact TPU program — while on a TPU the
compiled kernel runs), and outputs are reshaped and unpadded before return.

Paper contract (see docs/paper_map.md for the full table):

* ``client_sqnorms`` / ``tree_client_norms`` — Alg. 1 line 3 / Alg. 2 input:
  ``u_i = ||w_i U_i||``.
* ``masked_scale_aggregate`` / ``tree_masked_aggregate`` — Eq. 2's masked
  unbiased aggregate ``G = sum_i mask_i (w_i / p_i) U_i`` on one device.
* ``shard_masked_aggregate`` / ``sharded_masked_aggregate`` — the same Eq. 2
  contraction under a mesh: per-shard partial sum + one cross-shard ``psum``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compression import MATERIAL_ARITY
from repro.kernels.client_norm import client_sqnorms_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.masked_aggregate import masked_scale_aggregate_pallas
from repro.kernels.norm_aggregate import (
    compress_norm_scale_aggregate_pallas,
    norm_scale_aggregate_pallas,
)
from repro.kernels.sharded_aggregate import (
    sharded_compress_aggregate_pallas,
    sharded_masked_aggregate_pallas,
)
from repro.kernels.ssd_scan import ssd_scan_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# bytes of double-buffered streamed tiles one kernel may hold in VMEM: a
# quarter of the TPU's 16 MiB scoped limit, leaving the rest for the
# resident scale/norm blocks and the in-kernel temporaries of the compressor
TILE_BYTES = 4 << 20


def fit_chunk(chunk: int, d: int, rows: int, streams: int = 1) -> int:
    """The tile width a kernel streams: ``chunk`` capped by VMEM and by ``d``.

    ``rows`` is the tile height (the client count, or ``block_clients`` on
    the per-shard grid) and ``streams`` the number of ``(rows, chunk)``
    operands streamed per grid step (1 + the compressor's material arity).
    Each is double-buffered at 4 B per element (f32; rows padded to the
    8-row sublane tile), and the width is the largest multiple of 128 lanes
    whose ``2 x streams x rows x chunk x 4 B`` stays within ``TILE_BYTES``
    — never below 128 lanes.
    """
    rows = -(-max(rows, 1) // 8) * 8
    lanes = TILE_BYTES // (2 * streams * rows * 4) // 128 * 128
    return min(chunk, max(lanes, 128), max(d, 1))


def get_shard_map():
    """(shard_map callable, replication-check-off kwargs).

    Shared by every module that builds a shard_map (fl/shard_round.py,
    sim/pool.py, the mesh-level wrapper below), so each turns the
    replication check off the same way.
    """
    return jax.shard_map, {"check_vma": False}


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def client_sqnorms(updates: jax.Array, chunk: int = 4096, interpret: bool | None = None):
    """(clients, D) -> (clients,) f32 squared norms, fused single HBM pass."""
    if interpret is None:
        interpret = not _on_tpu()
    c, d = updates.shape
    chunk = fit_chunk(chunk, d, c)
    pad = (-d) % chunk
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
    return client_sqnorms_pallas(updates, chunk=chunk, interpret=interpret)


def tree_to_client_matrix(updates_tree) -> jax.Array:
    """Client-major ``(n, D)`` matrix of a pytree of ``(n, ...)`` leaves.

    One concatenated copy, in ``tree_leaves`` order — the canonical layout
    both client-axis kernels (sqnorms, masked aggregate) stream, and the one
    ``client_matrix_to_tree`` inverts.  All tree<->matrix conversions in the
    repo must go through this pair so the layouts cannot diverge.
    """
    leaves = jax.tree_util.tree_leaves(updates_tree)
    n = leaves[0].shape[0]
    return jnp.concatenate([l.reshape(n, -1) for l in leaves], axis=1)


def client_matrix_to_tree(vec: jax.Array, like_tree, strip_client_axis: bool,
                          keep_dtype: bool = False):
    """Split a flat ``(D,)`` vector back into ``like_tree``'s leaf layout.

    ``strip_client_axis``: leaves of ``like_tree`` carry a leading client axis
    not present in ``vec`` (i.e. ``vec`` is one aggregated row).  ``keep_dtype``
    casts each output leaf to its template leaf's dtype (else ``vec``'s dtype).
    """
    leaves, treedef = jax.tree_util.tree_flatten(like_tree)
    out, off = [], 0
    for leaf in leaves:
        shape = leaf.shape[1:] if strip_client_axis else leaf.shape
        size = leaf[0].size if strip_client_axis else leaf.size
        piece = vec[off:off + size].reshape(shape)
        out.append(piece.astype(leaf.dtype) if keep_dtype else piece)
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_client_norms(updates_tree, weights, chunk: int = 4096, interpret=None):
    """Kernel-backed equivalent of repro.core.ocs.client_norms."""
    flat = tree_to_client_matrix(updates_tree)
    sq = client_sqnorms(flat, chunk=chunk, interpret=interpret)
    return weights.astype(jnp.float32) * jnp.sqrt(sq)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def masked_scale_aggregate(updates: jax.Array, scale: jax.Array, chunk: int = 4096,
                           interpret: bool | None = None):
    """(clients, D), (clients,) -> (D,) f32 fused ``sum_i scale_i * U_i``.

    ``scale`` already folds the Bernoulli mask and the ``w_i / p_i`` OCS
    reweighting (zero for unsampled clients), so this is the whole masked
    aggregation in one HBM pass — no scaled ``(clients, D)`` intermediate.
    """
    if interpret is None:
        interpret = not _on_tpu()
    c, d = updates.shape
    chunk = fit_chunk(chunk, d, c)
    pad = (-d) % chunk
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
    out = masked_scale_aggregate_pallas(updates, scale.reshape(1, c),
                                        chunk=chunk, interpret=interpret)
    return out[0, :d]


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def norm_scale_aggregate(updates: jax.Array, scale: jax.Array, chunk: int = 4096,
                         interpret: bool | None = None):
    """(clients, D), (clients,) -> ((clients,) sq norms, (D,) aggregate), fused.

    Both OCS reductions from one HBM tile stream
    (kernels/norm_aggregate.py): the per-client squared norms behind
    ``u_i = ||w_i U_i||`` (Alg. 1 line 3) AND Eq. 2's contraction
    ``sum_i scale_i * U_i``.  The single-pass scan engine calls this on each
    cached / spill-recomputed group matrix post-plan: the aggregate is the
    payload, the squared norms come for free from the same tiles (a cache
    integrity signal against pass 1's norms).
    """
    if interpret is None:
        interpret = not _on_tpu()
    c, d = updates.shape
    chunk = fit_chunk(chunk, d, c)
    pad = (-d) % chunk
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
    sq, agg = norm_scale_aggregate_pallas(updates, scale.reshape(1, c),
                                          chunk=chunk, interpret=interpret)
    return sq[:, 0], agg[0, :d]


@partial(jax.jit, static_argnames=("kind", "param", "chunk", "interpret"))
def compress_norm_scale_aggregate(updates, scale, mats, kind: str, param: float,
                                  chunk: int = 4096,
                                  interpret: bool | None = None):
    """Raw (clients, D) + material -> ((clients,) sq norms of C(U),
    (D,) aggregate of C(U)) — compression fused into the aggregate stream.

    The in-stream form of compress -> Alg. 1 line 3 -> Eq. 2: the unbiased
    compressor runs elementwise on each VMEM tile (raw values + the
    ``MATERIAL_ARITY[kind]`` precomputed ``(clients, D)`` material matrices,
    streamed tile-for-tile), and both OCS reductions consume the compressed
    tile — one HBM read of each update, no ``C(U)`` intermediate ever
    written.  Padding follows the house convention: D pads to a ``chunk``
    multiple with zeros on updates AND material (zero in, zero out for every
    compressor kind), outputs are unpadded on return.
    """
    if interpret is None:
        interpret = not _on_tpu()
    c, d = updates.shape
    chunk = fit_chunk(chunk, d, c, 1 + MATERIAL_ARITY[kind])
    pad = (-d) % chunk
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
        mats = tuple(jnp.pad(m, ((0, 0), (0, pad))) for m in mats)
    sq, agg = compress_norm_scale_aggregate_pallas(
        updates, scale.reshape(1, c), tuple(mats), kind, param, chunk=chunk,
        interpret=interpret,
    )
    return sq[:, 0], agg[0, :d]


def tree_masked_aggregate(updates_tree, scale, chunk: int = 4096, interpret=None):
    """Kernel-backed masked aggregate over a pytree of (n, ...) leaves.

    Concatenates the tree into the client-major ``(n, D)`` matrix (the same
    layout ``tree_client_norms`` streams), runs the fused kernel, and splits
    the result back to the leaf shapes (cast to each leaf's dtype).

    Note the concatenate is itself one unscaled ``(n, D)`` copy: the kernel's
    single-pass / no-scaled-intermediate property holds for the flat matrix
    it streams, so the full win needs updates kept in that layout end-to-end
    (the ROADMAP's sharded-aggregation item); for an arbitrary pytree this
    wrapper trades the *scaled* intermediate for an unscaled one.
    """
    flat = tree_to_client_matrix(updates_tree)
    agg = masked_scale_aggregate(flat, scale, chunk=chunk, interpret=interpret)
    return client_matrix_to_tree(agg, updates_tree, strip_client_axis=True,
                                 keep_dtype=True)


def shard_masked_aggregate(updates, scale, axis_name: str | None = None,
                           chunk: int = 4096, block_clients: int = 128,
                           interpret: bool | None = None):
    """Shard-local ``(k, D)``, ``(k,)`` -> fully-summed ``(D,)`` f32 aggregate.

    The mesh-native form of Eq. 2, meant to be called INSIDE a ``shard_map``
    body whose client axis is ``axis_name``: the fused kernel contracts the
    local client block in one tile stream (kernels/sharded_aggregate.py), then
    one ``jax.lax.psum`` over ``axis_name`` completes ``sum_i scale_i U_i``
    across shards — the paper's "one partial sum per shard" uplink, with no
    replicated ``(n, D)`` materialisation anywhere.  ``axis_name=None`` skips
    the psum (single-shard / testing use).

    Same chunk/pad/interpret conventions as ``client_sqnorms``: ``D`` pads to
    a ``chunk`` multiple, the local client count pads to ``block_clients``
    (padding rows carry zero scale, contributing nothing), ``interpret``
    defaults by backend detection.
    """
    if interpret is None:
        interpret = not _on_tpu()
    c, d = updates.shape
    block_clients = min(block_clients, max(c, 1))
    chunk = fit_chunk(chunk, d, block_clients)
    pad_d = (-d) % chunk
    pad_c = (-c) % block_clients
    if pad_d or pad_c:
        updates = jnp.pad(updates, ((0, pad_c), (0, pad_d)))
        scale = jnp.pad(scale, (0, pad_c))
    out = sharded_masked_aggregate_pallas(
        updates, scale.reshape(1, -1), chunk=chunk,
        block_clients=block_clients, interpret=interpret,
    )[0, :d]
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out


def tree_shard_masked_aggregate(updates_tree, scale, axis_name: str | None = None,
                                chunk: int = 4096, block_clients: int = 128,
                                interpret=None):
    """Eq. 2 over a shard-local pytree of ``(k, ...)`` leaves, inside shard_map.

    Concatenates the LOCAL client block into its ``(k, D)`` client-major
    matrix (a per-shard copy — never the replicated ``(n, D)`` flatten of
    ``tree_masked_aggregate``), contracts it through the fused per-shard
    kernel, psums once over ``axis_name``, and splits the aggregated ``(D,)``
    row back to the leaf shapes (cast to each leaf's dtype).
    """
    flat = tree_to_client_matrix(updates_tree)
    agg = shard_masked_aggregate(
        flat, scale, axis_name=axis_name, chunk=chunk,
        block_clients=block_clients, interpret=interpret,
    )
    return client_matrix_to_tree(agg, updates_tree, strip_client_axis=True,
                                 keep_dtype=True)


def shard_compress_aggregate(updates, scale, mats, kind: str, param: float,
                             axis_name: str | None = None, chunk: int = 4096,
                             block_clients: int = 128,
                             interpret: bool | None = None):
    """Shard-local RAW ``(k, D)`` block + material -> ``((k,) sq norms of
    C(U), fully-summed (D,) f32 aggregate of C(U))``, compression fused.

    The mesh-native form of compress -> Eq. 2, meant to be called INSIDE a
    ``shard_map`` body: the fused kernel compresses each tile in-stream
    (kernels/sharded_aggregate.py) and contracts the local partial, then one
    ``jax.lax.psum`` over ``axis_name`` completes the estimator across
    shards — still "scalars up, one partial sum per shard", now with no
    compressed intermediate anywhere.  ``axis_name=None`` skips the psum
    (single-shard / testing use).  Pads D to a ``chunk`` multiple and the
    local client count to ``block_clients`` with zeros on updates, scale AND
    material (zero rows/columns contribute to neither output).
    """
    if interpret is None:
        interpret = not _on_tpu()
    c, d = updates.shape
    block_clients = min(block_clients, max(c, 1))
    chunk = fit_chunk(chunk, d, block_clients, 1 + MATERIAL_ARITY[kind])
    pad_d = (-d) % chunk
    pad_c = (-c) % block_clients
    if pad_d or pad_c:
        updates = jnp.pad(updates, ((0, pad_c), (0, pad_d)))
        scale = jnp.pad(scale, (0, pad_c))
        mats = tuple(jnp.pad(m, ((0, pad_c), (0, pad_d))) for m in mats)
    sq, out = sharded_compress_aggregate_pallas(
        updates, scale.reshape(1, -1), tuple(mats), kind, param, chunk=chunk,
        block_clients=block_clients, interpret=interpret,
    )
    sq, out = sq[:c, 0], out[0, :d]
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return sq, out


def tree_shard_compress_aggregate(updates_tree, scale, mats, kind: str,
                                  param: float, axis_name: str | None = None,
                                  chunk: int = 4096, block_clients: int = 128,
                                  interpret=None):
    """Fused compress+Eq. 2 over a shard-local pytree of RAW ``(k, ...)``
    leaves, inside shard_map.

    Concatenates the local block and each material pytree into their
    client-major ``(k, D)`` matrices (per-shard copies, never a replicated
    ``(n, D)`` flatten), streams them through the fused per-shard kernel
    (compression applied in-tile), psums once over ``axis_name``, and splits
    the aggregated ``(D,)`` row back to the leaf shapes (cast to each leaf's
    dtype).  The squared norms the stream re-emits are discarded here — the
    plan's norms come from the shared jnp path, which is what keeps masks
    bitwise identical across engines.
    """
    flat = tree_to_client_matrix(updates_tree)
    mat_flats = tuple(tree_to_client_matrix(m) for m in mats)
    _, agg = shard_compress_aggregate(
        flat, scale, mat_flats, kind, param, axis_name=axis_name, chunk=chunk,
        block_clients=block_clients, interpret=interpret,
    )
    return client_matrix_to_tree(agg, updates_tree, strip_client_axis=True,
                                 keep_dtype=True)


def sharded_masked_aggregate(updates, scale, mesh, client_axis: str = "data",
                             chunk: int = 4096, block_clients: int = 128,
                             interpret: bool | None = None):
    """Global ``(n, D)``, ``(n,)`` -> ``(D,)`` f32 aggregate under ``mesh``.

    Standalone mesh-level entry point: shard_maps the per-shard kernel over
    ``client_axis`` (each shard streams only its own ``(n/axis_size, D)``
    block) and finishes with the single cross-shard psum.  Drop-in replacement
    for ``masked_scale_aggregate`` when a mesh is active; ``n`` must divide by
    the axis size (the FL configs guarantee this).
    """
    n = updates.shape[0]
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[client_axis]
    assert n % axis_size == 0, (n, axis_size)
    smap, check = get_shard_map()
    fn = partial(
        shard_masked_aggregate, axis_name=client_axis, chunk=chunk,
        block_clients=block_clients, interpret=interpret,
    )
    return smap(
        fn, mesh=mesh,
        in_specs=(P(client_axis), P(client_axis)),
        out_specs=P(),
        **check,
    )(updates, scale)


@partial(jax.jit, static_argnames=("window", "prefix", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, window=None, prefix=0, block_q=128, block_k=128,
                    interpret: bool | None = None):
    """(BH, S, d) causal flash attention (optional window / prefix-LM)."""
    if interpret is None:
        interpret = not _on_tpu()
    return flash_attention_pallas(
        q, k, v, window=window, prefix=prefix,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, b, c, dt, da, *, chunk=128, interpret: bool | None = None):
    """Chunked SSD scan (Mamba2).  x:(BH,S,P) b,c:(BH,S,N) dt,da:(BH,S).
    Pads S to a chunk multiple with dt=0 identity steps."""
    if interpret is None:
        interpret = not _on_tpu()
    bh, s, p = x.shape
    pad = (-s) % chunk
    if pad:
        zpad = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, b, c, dt, da = map(zpad, (x, b, c, dt, da))
    y, state = ssd_scan_pallas(x, b, c, dt, da, chunk=chunk, interpret=interpret)
    return y[:, :s], state
