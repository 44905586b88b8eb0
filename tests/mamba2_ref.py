"""A plain Mamba-2 language model: the reference the program's
``mamba2-130m`` is tested against.

Written from the Mamba-2 paper (arXiv:2405.21060, Sec. 3 and 7: the SSD
layer and its block) and the published ``state-spaces/mamba2-130m``
config, in float32 under ``jax.default_matmul_precision("highest")``, with
no chunking, no kernel and no cache.  It imports nothing of
``repro.models`` and reads every size from the parameter tree it is given
(the program's own layout, so both run on the same seeded weights):

- ``embed.embedding`` (V, d), tied with the output head, and
  ``embed.final_norm.scale`` (d,);
- ``layers.norm.scale`` (L, d) and ``layers.mamba.*`` stacked over the L
  residual blocks ``h <- h + Mixer(RMSNorm(h))``.

The mixer: ``in_proj`` (no bias) to ``z`` (d_in), ``xBC`` (d_in + 2N) and
``dt`` (H); a causal depthwise convolution of width K with bias over
``xBC``, then SiLU; ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
the SSD over H heads of P = d_in / H with one group of B, C of width N, in
its quadratic dual form

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t,

the segment sums taken directly (a cumulative sum over ``r`` of the
masked ``dt_r A``), not as a difference of two long running sums; the
gated RMSNorm ``RMSNorm(y * SiLU(z))`` over all of d_in; ``out_proj`` (no
bias).  Then a final RMSNorm, the tied head and the mean next-token
cross-entropy against ``targets``.

Departures from the published model, as the program's configuration
files them (its ``reduced`` list): the RMSNorm epsilon is ``eps``, 1e-6 by
default, where the source uses 1e-5; the residual stream is kept in
float32 here, as the source does, while the program keeps it in its
parameter dtype (float32 at the tested size, so the two agree there); the
vocabulary is whatever the embedding holds (the program pads 50,277 to
50,280 rows, the source to 50,288).  The source's SSD chunk size (256,
the program's 128) is an algorithm tile and has no counterpart here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _causal_conv(x, w, b):
    """Depthwise causal convolution: ``out_t = sum_i w[:, i] x_{t-K+1+i} + b``
    over (B, S, C) with ``w`` (C, K) and zeros before the sequence."""
    k = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * w[:, i] for i in range(k)) + b


def segment_sums(da):
    """``seg[b, h, t, s] = sum_{s<r<=t} da[b, r, h]`` for ``s <= t`` and
    ``-inf`` above the diagonal, from ``da`` (B, S, H)."""
    s = da.shape[1]
    rows = jnp.moveaxis(da, 1, 2)[..., :, None]                   # (B,H,S,1): r
    below = jnp.tril(jnp.ones((s, s), bool), -1)                  # r > s
    seg = jnp.cumsum(jnp.where(below, rows, 0.0), axis=-2)        # sum over r <= t
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), seg, -jnp.inf)


def ssd(xs, dt, a, bm, cm, d_skip):
    """The SSD's quadratic dual form: ``xs`` (B,S,H,P), ``dt`` (B,S,H),
    ``a`` (H,), ``bm``/``cm`` (B,S,N), ``d_skip`` (H,) -> (B,S,H,P)."""
    decay = jnp.exp(segment_sums(dt * a))                         # (B,H,T,S)
    cb = jnp.einsum("btn,bsn->bts", cm, bm)
    w = cb[:, None] * decay * jnp.moveaxis(dt, 1, 2)[:, :, None, :]
    y = jnp.einsum("bhts,bshp->bthp", w, xs)
    return y + d_skip[:, None] * xs


def mixer(p, x, eps):
    """One Mamba-2 mixer over (B, S, d)."""
    bsz, s, _ = x.shape
    heads = p["A_log"].shape[-1]
    d_in = p["norm_scale"].shape[-1]
    n = (p["conv_w"].shape[0] - d_in) // 2
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :d_in]
    xbc = jax.nn.silu(_causal_conv(zxbcdt[..., d_in:2 * d_in + 2 * n],
                                   p["conv_w"], p["conv_b"]))
    dt = jax.nn.softplus(zxbcdt[..., 2 * d_in + 2 * n:] + p["dt_bias"])
    xs = xbc[..., :d_in].reshape(bsz, s, heads, d_in // heads)
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), xbc[..., d_in:d_in + n],
            xbc[..., d_in + n:], p["D"])
    y = _rms(y.reshape(bsz, s, d_in) * jax.nn.silu(z), p["norm_scale"], eps)
    return y @ p["out_proj"]


def logits(params, tokens, eps: float = 1e-6):
    """(B, S) token ids -> (B, S, V) float32 logits."""
    params = jax.tree_util.tree_map(lambda t: jnp.asarray(t, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embedding"]
        h = emb[tokens]
        layers = params["layers"]
        for i in range(layers["norm"]["scale"].shape[0]):
            lp = jax.tree_util.tree_map(lambda t: t[i], layers)
            h = h + mixer(lp["mamba"], _rms(h, lp["norm"]["scale"], eps), eps)
        h = _rms(h, params["embed"]["final_norm"]["scale"], eps)
        return h @ emb.T


def loss(params, batch, eps: float = 1e-6):
    """Mean next-token cross-entropy of ``batch["tokens"]`` against
    ``batch["targets"]``."""
    z = logits(params, batch["tokens"], eps)
    gold = jnp.take_along_axis(z, batch["targets"][..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)
