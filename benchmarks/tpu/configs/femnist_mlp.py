"""femnist_mlp: a FEMNIST-sized pool under the paper's OCS round, its data
and plain reference.

Beside ``femnist_mlp.json`` (the sizes as run).  Holds what belongs to this
configuration alone: the client pool made from the seed (LEAF's 3,550
writers), the weights made from the seed, the model FLOPs of a round, and
the plain reference of the model (the program's own MLP ``784 -> 64 -> 64
-> 62``, ReLU, softmax cross-entropy; it has no published source) that the
OCS round of ``tpubench.fl_ref`` trains.

The pool copies the repo's ``femnist_like`` generator (the paper's
footnote-6 unbalancing of dataset 1, class-conditional Gaussian images,
Dirichlet label skew), with two changes: the set of client sizes is drawn
once from a fixed generator and only permuted by the seed, so every seed
gives the program the same shapes and the same work; and the images'
noise is drawn on the device, so that making 3,550 clients' images takes
a fraction of a second of set-up.
"""

from __future__ import annotations

import numpy as np

from tpubench import costs, fl_ref
from tpubench.lowp import matmul

SIZES_SEED = 20101372   # fixed: the set of client sizes never depends on --seed
MEANS_SEED = 123457     # fixed class means, as the repo's generator has them
DATA_FOLD = 2           # the images' noise: fold_in(PRNGKey(seed), DATA_FOLD)


class Pool:
    """The client pool in the shape the sim driver reads: ``client_data``
    (one dict of arrays per client), ``n_clients`` and ``sizes()``."""

    def __init__(self, client_data: list):
        self.client_data = client_data

    @property
    def n_clients(self) -> int:
        return len(self.client_data)

    def sizes(self) -> np.ndarray:
        return np.array([len(d["y"]) for d in self.client_data])


def client_sizes(cfg: dict) -> np.ndarray:
    """The fixed set of client sizes (paper footnote 6, dataset 1)."""
    s, a, b = cfg["unbalance"]
    rng = np.random.default_rng(SIZES_SEED)
    out = []
    while len(out) < cfg["pool_clients"]:
        n = int(rng.lognormal(np.log(cfg["base_examples"]), 0.5))
        n = max(8, min(n, cfg["max_examples"]))
        if a < n < b:
            if rng.random() < s:
                continue
            n = a
        out.append(n)
    sizes = np.asarray(out)
    sizes[np.argmax(sizes)] = cfg["max_examples"]   # pin the padded pool shape
    return sizes


def make_pool(cfg: dict, seed: int) -> Pool:
    """The pool from the seed: each client's labels on the host, then every
    image in one jitted call on the device (``means[y] + 0.25 noise``),
    fetched once and split by client."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    means = (np.random.default_rng(MEANS_SEED)
             .normal(size=(cfg["num_classes"], cfg["input_dim"])).astype(np.float32)
             * 4.0 / np.sqrt(cfg["input_dim"]))
    sizes = rng.permutation(client_sizes(cfg))
    labels = []
    for n in sizes:
        probs = rng.dirichlet(np.full(cfg["num_classes"], cfg["dirichlet"]))
        labels.append(rng.choice(cfg["num_classes"], size=int(n), p=probs).astype(np.int32))
    y = np.concatenate(labels)

    @jax.jit
    def images(key, y, means):
        noise = jax.random.normal(key, (y.shape[0], means.shape[1]), jnp.float32)
        return means[y] + 0.25 * noise

    key = jax.random.fold_in(jax.random.PRNGKey(seed), DATA_FOLD)
    x = np.asarray(jax.device_get(images(key, y, means)))
    cuts = np.cumsum(sizes)[:-1]
    return Pool([{"x": xc, "y": yc} for xc, yc in zip(np.split(x, cuts), labels)])


def make_init(cfg: dict):
    """``init(key) -> params``: one jitted call on the device, float32."""
    import jax
    import jax.numpy as jnp

    d, h, c = cfg["input_dim"], cfg["hidden"], cfg["num_classes"]

    @jax.jit
    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w1": jax.random.normal(k1, (d, h)) / np.sqrt(d),
            "b1": jnp.zeros((h,)),
            "w2": jax.random.normal(k2, (h, h)) / np.sqrt(h),
            "b2": jnp.zeros((h,)),
            "w3": jax.random.normal(k3, (h, c)) / np.sqrt(h),
            "b3": jnp.zeros((c,)),
        }

    return init


def fl_kwargs(cfg: dict) -> dict:
    """The round as ``FLConfig`` fields."""
    keys = ("n_clients", "expected_clients", "sampler", "j_max", "local_steps",
            "lr_local", "lr_global", "round_engine", "agg_backend")
    return {k: cfg[k] for k in keys}


def flops_per_round(cfg: dict) -> float:
    samples = cfg["n_clients"] * cfg["local_steps"] * cfg["batch_size"]
    return costs.train_flops(cfg["params"], samples)


def aggregate_itemsize(cfg: dict) -> int:
    return 4


def tiny(cfg: dict) -> dict:
    """A seconds-scale copy for the CPU rehearsal and tests (never measured):
    the published widths and local steps, a small pool and cohort."""
    return dict(cfg, pool_clients=24, base_examples=40, max_examples=160, n_clients=8,
                expected_clients=3)


# ------------------------------------------------------------ reference

def model_loss(mode: str):
    """Cross-entropy of the MLP with every product at precision ``mode``."""
    import jax
    import jax.numpy as jnp

    mm = matmul(mode)

    def loss(p, batch):
        h = jax.nn.relu(mm(batch["x"], p["w1"]) + p["b1"])
        h = jax.nn.relu(mm(h, p["w2"]) + p["b2"])
        logits = mm(h, p["w3"]) + p["b3"]
        gold = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)

    return loss


def reference(cfg: dict, pool: Pool, seed: int, rounds: int, mode: str = "highest") -> dict:
    """The first ``rounds`` OCS rounds from the seed, plainly.

    Returns ``losses (R,)``, ``norms (R, n)``, ``masks (R, n)``, the leaf
    norms of the first round's server update (``first``) and of the
    params' change after ``rounds`` rounds (``change``)."""
    import jax
    import jax.numpy as jnp

    loss_fn = model_loss(mode)
    mm = matmul(mode)
    key = jax.random.PRNGKey(seed)
    params0 = make_init(cfg)(jax.random.fold_in(key, fl_ref.PARAMS_FOLD))
    n = cfg["n_clients"]
    weights = jnp.full((n,), 1.0 / n, jnp.float32)

    @jax.jit
    def one_round(params, x, y, step_mask, round_key):
        upd, losses = jax.vmap(
            lambda bx, by, sm: fl_ref.local_update(
                loss_fn, params, {"x": bx, "y": by}, sm, cfg["lr_local"])
        )(x, y, step_mask)
        u = fl_ref.norms(upd, weights)
        _, mask, scale = fl_ref.plan(u, weights, cfg["expected_clients"],
                                     cfg["j_max"], round_key)
        agg = jax.tree_util.tree_map(
            lambda t: mm(scale[None, :], t.reshape(n, -1)).reshape(t.shape[1:]), upd)
        return fl_ref.server_step(params, agg, cfg["lr_global"]), jnp.mean(losses), u, mask

    rng = np.random.default_rng(seed)
    sizes = pool.sizes()
    params, out = params0, {"losses": [], "norms": [], "masks": []}
    for k in range(rounds):
        clients, take, step_mask = fl_ref.replay_cohort(
            rng, sizes, n, cfg["local_steps"], cfg["batch_size"], cfg["local_epoch"])
        x = np.stack([pool.client_data[c]["x"][t] for c, t in zip(clients, take)])
        y = np.stack([pool.client_data[c]["y"][t] for c, t in zip(clients, take)])
        params, loss, u, mask = one_round(
            params, x, y, step_mask, jax.random.fold_in(key, fl_ref.ROUND_FOLD + k))
        out["losses"].append(float(loss))
        out["norms"].append(np.asarray(u, np.float64))
        out["masks"].append(np.asarray(mask, bool))
        if k == 0:
            out["first"] = fl_ref.leaf_norms(fl_ref.tree_diff(params0, params))
    out["change"] = fl_ref.leaf_norms(fl_ref.tree_diff(params0, params))
    return out

CONTROL = "high"   # the control: float32 products at "high", one step below "highest"
