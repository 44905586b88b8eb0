"""Chunked SSD (Mamba2) scan Pallas kernel — the SSM families' compute
hot-spot (zamba2-2.7b carries 45 Mamba2 blocks; mamba2-130m is pure SSD).

TPU adaptation of the SSD algorithm (arXiv:2405.21060):
* Grid: (batch*heads, num_chunks) with the chunk axis innermost — TPU grids
  iterate sequentially, so the recurrent (P, N) state lives in a VMEM
  scratch buffer and is carried across chunk steps for free (the same trick
  the flash kernel uses for its softmax carries).
* Per step, the (Q, Q) intra-chunk attention-like matmul and the (Q, P) x
  (Q, N) state outer products map onto the MXU; Q (chunk), P (head_dim) and
  N (state) are 64/128-aligned.
* Everything for one (batch*head, chunk) tile — x (Q,P), B/C (Q,N), dt/dA
  (Q,) — fits comfortably in VMEM.

Validated in interpret mode against ``ref.ssd_scan_ref`` (which itself
mirrors repro.models.ssm's fused-scan path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, da_ref, y_ref, state_out_ref,
                state_ref, *, chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)       # (Q, P)
    b = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)       # (Q, N)
    dt = dt_ref[0, 0].astype(jnp.float32)     # (Q,)
    da = da_ref[0, 0].astype(jnp.float32)     # (Q,)

    a_cs = jnp.cumsum(da)                     # (Q,)
    # intra-chunk: y_diag[s] = sum_{t<=s} exp(a_cs[s]-a_cs[t]) dt[t] (c_s.b_t) x_t
    seg = a_cs[:, None] - a_cs[None, :]
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    # masked before the exponent, as in repro.models.ssm: above the diagonal
    # seg >= 0 can overflow, and where() after exp() has a NaN gradient there
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    att = cb * decay * dt[None, :]
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)

    # inter-chunk: contribution of the incoming state, then state update
    state = state_ref[...]                    # (P, N)
    y += jnp.exp(a_cs)[:, None] * jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    a_tot = a_cs[-1]
    decay_out = jnp.exp(a_tot - a_cs) * dt    # (Q,)
    s_chunk = jax.lax.dot_general(
        x * decay_out[:, None], b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # (P, N)
    state = state * jnp.exp(a_tot) + s_chunk
    state_ref[...] = state

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit_state():
        state_out_ref[0] = state.astype(state_out_ref.dtype)


def ssd_scan_pallas(x, b, c, dt, da, *, chunk=128, interpret=False):
    """x: (BH, S, P); b, c: (BH, S, N); dt, da: (BH, S).

    Returns (y (BH,S,P) f32, final_state (BH,P,N) f32).  S must be a chunk
    multiple (the ops.py wrapper pads with dt=0 identity steps).
    """
    bh, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    xr = x.reshape(bh, nc, chunk, p)
    br = b.reshape(bh, nc, chunk, n)
    cr = c.reshape(bh, nc, chunk, n)
    dtr = dt.reshape(bh, nc, chunk)
    dar = da.reshape(bh, nc, chunk)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, p, n), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, chunk, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, br, cr, dtr, dar)
    return y.reshape(bh, s, p), state
