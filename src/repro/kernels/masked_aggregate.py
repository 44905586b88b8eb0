"""Fused masked scale-&-aggregate — the OCS estimator's cross-client sum
(paper Eq. 2): ``G = sum_i mask_i * (w_i / p_i) * U_i``.

The naive jnp lowering materialises the scaled per-client matrix
``scale[:, None] * U`` (another ``(n, D)`` HBM tensor, written and re-read)
before the client-axis reduction.  This kernel streams ``(clients, chunk)``
tiles HBM->VMEM and contracts the client axis in-register: each grid step
reads one tile, multiplies by the per-client scale vector (zero for unsampled
clients, so masking is folded into the contraction) and writes one ``(1, chunk)``
slice of the aggregate — a single pass over HBM, no scaled intermediate.

Paired with ``client_norm.client_sqnorms_pallas`` this makes the whole OCS
critical path (norms -> probabilities -> masked aggregate) single-pass over
the update matrix.

Grid: (num_chunks,).  Blocks: updates ``(C, CHUNK)`` tile of the ``(C, D)``
client-major matrix; the ``(1, C)`` scale row maps to the same block every
step (it stays resident in VMEM); output block ``(1, CHUNK)`` of the
``(1, D)`` aggregate row at chunk ``i``.  The contraction itself is a
``(1, C) @ (C, CHUNK)`` matmul — every operand 2-D, which is the form the
TPU compiler accepts (it refuses a dot whose operand is a 1-D vector) — and
each output element is touched by exactly one grid step so no cross-step
accumulation is needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def scale_contract(s, x):
    """``(1, C) @ (C, CHUNK)`` -> ``(1, CHUNK)`` f32: Eq. 2 on one VMEM tile.

    The one contraction every aggregate kernel runs.  ``HIGHEST`` precision
    asks the MXU for full f32 products, so the kernels agree with the f32
    oracles in kernels/ref.py to f32 rounding.
    """
    return jax.lax.dot_general(
        s.astype(jnp.float32), x.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _masked_agg_kernel(s_ref, x_ref, o_ref):
    o_ref[...] = scale_contract(s_ref[...], x_ref[...])


def masked_scale_aggregate_pallas(
    updates: jax.Array, scale: jax.Array, chunk: int = 4096, interpret: bool = False
) -> jax.Array:
    """updates: (clients, D), scale: (1, clients) -> (1, D) f32 aggregate.

    D is padded to a multiple of ``chunk`` by the wrapper in ops.py, which
    also carries the scale vector and the aggregate as rows.
    """
    c, d = updates.shape
    assert scale.shape == (1, c), (scale.shape, c)
    assert d % chunk == 0, (d, chunk)
    grid = (d // chunk,)
    return pl.pallas_call(
        _masked_agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((c, chunk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, chunk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(scale, updates)
