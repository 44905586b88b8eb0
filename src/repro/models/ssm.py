"""Mamba2 / SSD (state-space duality) block  [arXiv:2405.21060].

Chunked quadratic-within-chunk + linear-across-chunk algorithm (SSD):
sequences are split into chunks of ``ssm_chunk``; within a chunk the
attention-like masked form is used, across chunks a `lax.scan` carries the
(B, H, P, N) recurrent state.  Decode is the O(1) single-token recurrence.

TPU adaptation: the head dimension (d_inner = expand * d_model) is the
'model'-sharded axis; the state size N is small and replicated; the
cross-chunk scan is sequential per device (no collectives), so SSM layers
contribute no attention-like collective traffic — visible in the roofline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init


def dims(cfg: ModelConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_head_dim
    conv_dim = d_in + 2 * cfg.ssm_state
    return d_in, nheads, conv_dim


def init_mamba2(key, cfg: ModelConfig):
    d = cfg.d_model
    d_in, nheads, conv_dim = dims(cfg)
    n = cfg.ssm_state
    ks = jax.random.split(key, 4)
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * d_in + 2 * n + nheads)),
        "conv_w": _dense_init(ks[1], (conv_dim, cfg.ssm_conv), in_axis=1),
        "conv_b": jnp.zeros((conv_dim,)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)),
        "D": jnp.ones((nheads,)),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((nheads,), 0.01))),  # softplus^-1
        "norm_scale": jnp.ones((d_in,)),
        "out_proj": _dense_init(ks[3], (d_in, d)),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv.  x: (B, S, C), w: (C, K)."""
    k = w.shape[-1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + pad[:, i : i + x.shape[1], :] * w[:, i]
    return out + b


def _split(zxbcdt, cfg: ModelConfig):
    d_in, nheads, _ = dims(cfg)
    n = cfg.ssm_state
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in : d_in + d_in + 2 * n]
    dt = zxbcdt[..., -nheads:]
    return z, xbc, dt


def _masked_decay(seg, tri):
    """``exp(seg)`` on and below the diagonal, 0 above it.

    ``seg[s, t] = sum_{t<r<=s} dt_r A`` is <= 0 where ``t <= s``, but above
    the diagonal it is the sum with its sign flipped, >= 0, and its
    exponent overflows once ``sum dt |A|`` over a chunk passes ~88.7.  So the
    mask goes in before the exponent: ``where(tri, exp(seg), 0)`` would
    still give the right values, yet its gradient multiplies the masked
    entries' zero cotangent by ``exp(seg) = inf``, which is NaN.
    """
    return jnp.exp(jnp.where(tri, seg, -jnp.inf))


def _gated_norm(y, z, scale, eps=1e-6):
    y = y * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    return y * jax.lax.rsqrt(ms + eps) * scale


def apply_mamba2(params, x, cfg: ModelConfig):
    """Training/prefill forward.  x: (B, S, d) -> (y, final_state).

    final_state = (ssm_state (B,H,P,N), conv_state (B, K-1, conv_dim)) so that
    prefill can seed decoding.
    """
    bsz, true_seq, _ = x.shape
    d_in, nheads, conv_dim = dims(cfg)
    n, p, q = cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_chunk
    # pad to a chunk multiple; padded steps get dt = 0 (identity recurrence)
    pad = (-true_seq) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    seq = true_seq + pad
    nc = seq // q

    zxbcdt = x @ params["in_proj"]
    z, xbc_pre, dt = _split(zxbcdt, cfg)
    xbc = jax.nn.silu(_causal_conv(xbc_pre, params["conv_w"], params["conv_b"]))
    xs = xbc[..., :d_in].reshape(bsz, seq, nheads, p)
    bmat = xbc[..., d_in : d_in + n]                       # (B,S,N)
    cmat = xbc[..., d_in + n :]                            # (B,S,N)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,S,H)
    if pad:
        valid = (jnp.arange(seq) < true_seq)[None, :, None]
        dt = jnp.where(valid, dt, 0.0)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))                 # (H,)
    # da <= 0, so a_cs (its running sum in a chunk) falls: exp(a_cs),
    # exp(a_tot - a_cs) and exp(a_tot) below all take arguments <= 0
    da = dt * a                                                        # (B,S,H)

    # chunk
    xs_c = xs.reshape(bsz, nc, q, nheads, p).astype(jnp.float32)
    b_c = bmat.reshape(bsz, nc, q, n).astype(jnp.float32)
    c_c = cmat.reshape(bsz, nc, q, n).astype(jnp.float32)
    dt_c = dt.reshape(bsz, nc, q, nheads)
    da_c = da.reshape(bsz, nc, q, nheads)

    if nc > 64:
        # long-sequence path: one fused scan over chunks — O(B*Q*Q*H) live
        # memory instead of O(B*NC*Q*Q*H) (needed for 32k+ prefill).
        tri = jnp.tril(jnp.ones((q, q), bool))

        def chunk_step(state, inp):
            x_i, b_i, c_i, dt_i, da_i = inp  # (B,Q,...) for this chunk
            a_cs = jnp.cumsum(da_i, axis=1)                       # (B,Q,H)
            with jax.named_scope("ssd_intra"):
                seg = a_cs[:, :, None, :] - a_cs[:, None, :, :]   # (B,Q,Q,H)
                decay = _masked_decay(seg, tri[None, :, :, None])
                cb = jnp.einsum("bsn,btn->bst", c_i, b_i)
                att = cb[..., None] * decay * dt_i[:, None, :, :]
                y_diag = jnp.einsum("bsth,bthp->bshp", att, x_i)
            with jax.named_scope("ssd_inter"):
                y_off = jnp.einsum("btn,bth,bhpn->bthp", c_i, jnp.exp(a_cs), state)
            with jax.named_scope("ssd_states"):
                a_tot = a_cs[:, -1, :]
                decay_out = jnp.exp(a_tot[:, None, :] - a_cs)
                s_chunk = jnp.einsum("bth,btn,bthp->bhpn", decay_out * dt_i, b_i, x_i)
                new_state = state * jnp.exp(a_tot)[:, :, None, None] + s_chunk
            return new_state, y_diag + y_off

        init = jnp.zeros((bsz, nheads, p, n), jnp.float32)
        mv = lambda t: jnp.moveaxis(t, 1, 0)
        final_state, ys = jax.lax.scan(
            chunk_step, init, (mv(xs_c), mv(b_c), mv(c_c), mv(dt_c), mv(da_c))
        )
        y = jnp.moveaxis(ys, 0, 1).reshape(bsz, seq, nheads, p)
        y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
        y = y.reshape(bsz, seq, d_in)
        y = _gated_norm(y, z, params["norm_scale"])
        out = (y @ params["out_proj"].astype(jnp.float32)).astype(x.dtype)
        if pad:
            out = out[:, :true_seq]
        conv_state = jax.lax.dynamic_slice_in_dim(
            xbc_pre, true_seq - (cfg.ssm_conv - 1), cfg.ssm_conv - 1, axis=1
        )
        return out, (final_state, conv_state)

    a_cs = jnp.cumsum(da_c, axis=2)                                   # (B,NC,Q,H)

    # intra-chunk (quadratic within chunk)
    with jax.named_scope("ssd_intra"):
        seg = a_cs[:, :, :, None, :] - a_cs[:, :, None, :, :]         # (B,NC,Q,Q,H)
        tri = jnp.tril(jnp.ones((q, q), bool))
        decay = _masked_decay(seg, tri[None, None, :, :, None])
        cb = jnp.einsum("bcsn,bctn->bcst", c_c, b_c)                  # (B,NC,Q,Q)
        att = cb[..., None] * decay * dt_c[:, :, None, :, :]          # (B,NC,Q,Q,H)
        y_diag = jnp.einsum("bcsth,bcthp->bcshp", att, xs_c)

    with jax.named_scope("ssd_states"):
        # chunk states: S_c = sum_t exp(a_total - a_cs[t]) dt[t] B_t (x) x_t
        a_tot = a_cs[:, :, -1, :]                                     # (B,NC,H)
        decay_out = jnp.exp(a_tot[:, :, None, :] - a_cs)              # (B,NC,Q,H)
        s_chunk = jnp.einsum(
            "bcth,bctn,bcthp->bchpn", decay_out * dt_c, b_c, xs_c
        )                                                              # (B,NC,H,P,N)

        # inter-chunk recurrence
        def scan_fn(state, inp):
            s_c, atot = inp
            new = state * jnp.exp(atot)[:, :, None, None] + s_c
            return new, state  # emit the state *entering* this chunk

        init = jnp.zeros((bsz, nheads, p, n), jnp.float32)
        final_state, states_in = jax.lax.scan(
            scan_fn,
            init,
            (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(a_tot, 1, 0)),
        )
        states_in = jnp.moveaxis(states_in, 0, 1)                      # (B,NC,H,P,N)

    # inter-chunk contribution
    with jax.named_scope("ssd_inter"):
        y_off = jnp.einsum(
            "bctn,bcth,bchpn->bcthp", c_c, jnp.exp(a_cs), states_in
        )
    y = (y_diag + y_off).reshape(bsz, seq, nheads, p)
    y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, seq, d_in)
    y = _gated_norm(y, z, params["norm_scale"])
    out = (y @ params["out_proj"].astype(jnp.float32)).astype(x.dtype)
    if pad:
        out = out[:, :true_seq]

    conv_state = jax.lax.dynamic_slice_in_dim(
        xbc_pre, true_seq - (cfg.ssm_conv - 1), cfg.ssm_conv - 1, axis=1
    )                                                                  # (B,K-1,C)
    return out, (final_state, conv_state)


def init_state(cfg: ModelConfig, batch: int):
    d_in, nheads, conv_dim = dims(cfg)
    return (
        jnp.zeros((batch, nheads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), jnp.float32),
    )


def decode_mamba2(params, x, state, cfg: ModelConfig):
    """Single-token decode.  x: (B, 1, d), state from init_state/apply."""
    bsz = x.shape[0]
    d_in, nheads, conv_dim = dims(cfg)
    n, p = cfg.ssm_state, cfg.ssm_head_dim
    ssm_state, conv_state = state

    zxbcdt = x[:, 0, :] @ params["in_proj"]                            # (B, ...)
    z, xbc_pre, dt = _split(zxbcdt, cfg)
    # conv over the buffered window
    window = jnp.concatenate([conv_state, xbc_pre[:, None, :]], axis=1)  # (B,K,C)
    conv_out = jnp.einsum("bkc,ck->bc", window, params["conv_w"]) + params["conv_b"]
    xbc = jax.nn.silu(conv_out)
    xt = xbc[:, :d_in].reshape(bsz, nheads, p).astype(jnp.float32)
    bt = xbc[:, d_in : d_in + n].astype(jnp.float32)
    ct = xbc[:, d_in + n :].astype(jnp.float32)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])   # (B,H)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt * a)                                            # (B,H)

    new_state = ssm_state * decay[:, :, None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt, bt, xt
    )
    y = jnp.einsum("bn,bhpn->bhp", ct, new_state)
    y = y + params["D"][None, :, None] * xt
    y = y.reshape(bsz, d_in)
    y = _gated_norm(y, z, params["norm_scale"])
    out = (y @ params["out_proj"].astype(jnp.float32)).astype(x.dtype)

    new_conv = jnp.concatenate([conv_state[:, 1:, :], xbc_pre[:, None, :]], axis=1)
    return out[:, None, :], (new_state, new_conv)
