"""mamba2_130m: Mamba-2 130M as the federated client model, and its plain
reference.

Beside ``mamba2_130m.json`` (the sizes as run).  Holds the weights and the
token batches made from the seed on the device, the model FLOPs of a
round, and a plain float32 reference of the model written from the
Mamba-2 paper (arXiv:2405.21060) and the published config: tied
embeddings; 24 residual blocks ``h + Mixer(RMSNorm(h))``; the mixer's input
projection to ``z``, ``xBC`` and ``dt``; a causal depthwise convolution of
width 4 and SiLU over ``xBC``; the SSD recurrence over 24 heads of 64 with
a 128-wide state (one group), computed here in its quadratic dual form
``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t``
(no chunking); the gated RMSNorm ``RMSNorm(y * SiLU(z))``; the output
projection; a final RMSNorm and the tied head.  The FL round around it is
``tpubench.fl_ref``: parameters stored in bfloat16, as the configuration
states, each local step computed in float32 from them and rounded back.
"""

from __future__ import annotations

import numpy as np

from tpubench import costs, fl_ref
from tpubench.lowp import matmul

CONTROL = "fp8"   # the control: the bfloat16 products in float8 e4m3


def dims(cfg: dict) -> dict:
    d_in = cfg["expand"] * cfg["d_model"]
    heads = d_in // cfg["headdim"]
    return {"d_in": d_in, "heads": heads, "conv": d_in + 2 * cfg["ngroups"] * cfg["d_state"],
            "proj": 2 * d_in + 2 * cfg["ngroups"] * cfg["d_state"] + heads}


def make_init(cfg: dict):
    """``init(key) -> params`` in the served dtype, one jitted call."""
    import jax
    import jax.numpy as jnp

    d, L, V = cfg["d_model"], cfg["n_layer"], cfg["vocab_size"]
    dm = dims(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])

    @jax.jit
    def init(key):
        k = jax.random.split(key, 4)
        heads = dm["heads"]
        layer = {
            "in_proj": jax.random.normal(k[1], (L, d, dm["proj"])) / np.sqrt(d),
            "conv_w": jax.random.normal(k[2], (L, dm["conv"], cfg["d_conv"]))
            / np.sqrt(cfg["d_conv"]),
            "conv_b": jnp.zeros((L, dm["conv"])),
            "A_log": jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, heads)), (L, heads)),
            "D": jnp.ones((L, heads)),
            "dt_bias": jnp.full((L, heads), np.log(np.expm1(0.01))),
            "norm_scale": jnp.ones((L, dm["d_in"])),
            "out_proj": jax.random.normal(k[3], (L, dm["d_in"], d)) / np.sqrt(dm["d_in"]),
        }
        p = {"embed": {"embedding": jax.random.normal(k[0], (V, d)) * 0.02,
                       "final_norm": {"scale": jnp.ones((d,))}},
             "layers": {"mamba": layer, "norm": {"scale": jnp.ones((L, d))}}}
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), p)

    return init


def make_inputs(cfg: dict, seed: int, count: int):
    """``count`` distinct round batches of uniform token ids (targets equal
    to the tokens) and round keys ``fold_in(PRNGKey(seed), k)``, made on the
    device in one jitted call."""
    import jax
    import jax.numpy as jnp

    shape = (cfg["n_clients"], cfg["local_steps"], cfg["batch"], cfg["seq_len"])

    @jax.jit
    def make(key):
        toks = jax.random.randint(jax.random.fold_in(key, 7), (count,) + shape, 0,
                                  cfg["vocab_size"], jnp.int32)
        keys = jax.vmap(lambda k: jax.random.fold_in(key, k))(jnp.arange(count))
        return tuple(toks[i] for i in range(count)), tuple(keys[i] for i in range(count))

    toks, keys = make(jax.random.PRNGKey(seed))
    return [{"tokens": t, "targets": t} for t in toks], list(keys)


def fl_kwargs(cfg: dict) -> dict:
    keys = ("n_clients", "expected_clients", "sampler", "j_max", "local_steps", "lr_local",
            "lr_global", "round_engine", "scan_group", "cache_groups", "agg_backend")
    return {k: cfg[k] for k in keys}


def program_fields(cfg: dict) -> dict:
    """The published sizes under the names of the program's ModelConfig."""
    return {"num_layers": cfg["n_layer"], "d_model": cfg["d_model"],
            "vocab_size": cfg["vocab_size"], "ssm_state": cfg["d_state"],
            "ssm_expand": cfg["expand"], "ssm_head_dim": cfg["headdim"],
            "ssm_conv": cfg["d_conv"], "ssm_chunk": cfg["chunk_size"],
            "tie_embeddings": cfg["tie_embeddings"], "dtype": cfg["param_dtype"]}


def flops_per_round(cfg: dict) -> float:
    tokens = cfg["n_clients"] * cfg["local_steps"] * cfg["batch"] * cfg["seq_len"]
    return costs.train_flops(cfg["params"], tokens)


def aggregate_itemsize(cfg: dict) -> int:
    """Bytes per element of the update rows the kernel streams (the
    parameter dtype)."""
    return {"bfloat16": 2, "float32": 4}[cfg["param_dtype"]]


def tiny(cfg: dict) -> dict:
    """The program's own reduced mamba2 (two layers of width 128), float32."""
    small = dict(cfg, d_model=128, n_layer=2, vocab_size=512, d_state=16, headdim=32,
                 chunk_size=16, param_dtype="float32", n_clients=4, batch=2, seq_len=32,
                 cache_groups=2)
    dm = dims(small)
    per_layer = (128 * dm["proj"] + dm["conv"] * 4 + dm["conv"] + 3 * dm["heads"]
                 + dm["d_in"] + dm["d_in"] * 128 + 128)
    small["params"] = 2 * per_layer + 512 * 128 + 128
    return small


# ------------------------------------------------------------ reference

def model_loss(cfg: dict, mode: str):
    """Mean next-token cross-entropy of the plain float32 model, its dense
    products (projections and head) at precision ``mode``."""
    import jax
    import jax.numpy as jnp

    mm = matmul(mode)
    dm = dims(cfg)
    n, hd, eps = cfg["d_state"], cfg["headdim"], cfg["norm_epsilon"]
    hi = jax.lax.Precision.HIGHEST

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale

    def conv(x, w, b):
        k = w.shape[-1]
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        return sum(xp[:, i:i + x.shape[1]] * w[:, i] for i in range(k)) + b

    def mixer(p, x):
        bsz, s, _ = x.shape
        zxbcdt = mm(x, p["in_proj"])
        z = zxbcdt[..., :dm["d_in"]]
        xbc = jax.nn.silu(conv(zxbcdt[..., dm["d_in"]:dm["d_in"] + dm["conv"]],
                               p["conv_w"], p["conv_b"]))
        dt = jax.nn.softplus(zxbcdt[..., -dm["heads"]:] + p["dt_bias"])      # (b,s,h)
        xs = xbc[..., :dm["d_in"]].reshape(bsz, s, dm["heads"], hd)
        bm = xbc[..., dm["d_in"]:dm["d_in"] + n]
        cm = xbc[..., dm["d_in"] + n:]
        cs = jnp.cumsum(dt * -jnp.exp(p["A_log"]), axis=1)                  # (b,s,h)
        seg = cs[:, :, None, :] - cs[:, None, :, :]                          # (b,t,s,h)
        causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum("btn,bsn->bts", cm, bm, precision=hi)
        w = cb[..., None] * decay * dt[:, None, :, :]
        y = jnp.einsum("btsh,bshp->bthp", w, xs, precision=hi)
        y = (y + p["D"][:, None] * xs).reshape(bsz, s, dm["d_in"])
        y = rms(y * jax.nn.silu(z), p["norm_scale"])
        return mm(y, p["out_proj"])

    def block(h, lp):
        return h + mixer(lp["mamba"], rms(h, lp["norm"]["scale"])), None

    def loss(p, batch):
        emb = p["embed"]["embedding"]
        h = emb[batch["tokens"]]
        h, _ = jax.lax.scan(jax.checkpoint(block), h, p["layers"])
        h = rms(h, p["embed"]["final_norm"]["scale"])
        logits = mm(h, emb.T)
        gold = jnp.take_along_axis(logits, batch["targets"][..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)

    return loss


def reference(cfg: dict, params0, batches: list, keys: list, rounds: int,
              mode: str = "highest") -> dict:
    """The first ``rounds`` OCS rounds from ``params0`` on ``batches`` and
    ``keys`` (what the benchmark made from the seed), one client at a time."""
    import jax
    import jax.numpy as jnp

    loss_fn = model_loss(cfg, mode)
    n = cfg["n_clients"]
    weights = jnp.full((n,), 1.0 / n, jnp.float32)
    step_mask = jnp.ones((cfg["local_steps"],), jnp.float32)

    @jax.jit
    def client(params, tokens):
        upd, loss = fl_ref.local_update(
            loss_fn, params, {"tokens": tokens, "targets": tokens}, step_mask,
            cfg["lr_local"])
        sq = sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(upd))
        return upd, loss, jnp.sqrt(sq)

    @jax.jit
    def accumulate(agg, upd, scale_i):
        return jax.tree_util.tree_map(lambda a, x: a + scale_i * x, agg, upd)

    @jax.jit
    def server(params, agg):
        return fl_ref.server_step(params, agg, cfg["lr_global"])

    params, out = params0, {"losses": [], "norms": [], "masks": []}
    for k in range(rounds):
        upds, losses, sq = [], [], []
        for i in range(n):
            u, loss, norm = client(params, batches[k]["tokens"][i])
            upds.append(u)
            losses.append(loss)
            sq.append(norm)
        u = weights * jnp.stack(sq)
        _, mask, scale = fl_ref.plan(u, weights, cfg["expected_clients"], cfg["j_max"],
                                     keys[k])
        agg = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        for i in range(n):
            agg = accumulate(agg, upds[i], scale[i])
        params = server(params, agg)
        del upds, agg
        out["losses"].append(float(jnp.mean(jnp.stack(losses))))
        out["norms"].append(np.asarray(u, np.float64))
        out["masks"].append(np.asarray(mask, bool))
        if k == 0:
            out["first"] = fl_ref.leaf_norms(fl_ref.tree_diff(params0, params))
    out["change"] = fl_ref.leaf_norms(fl_ref.tree_diff(params0, params))
    return out
