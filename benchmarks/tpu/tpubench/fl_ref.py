"""Plain reference of one federated OCS round (arXiv:2010.13723, Alg. 1-3).

Written from the paper and from the program's documented contracts (its
key discipline and its cohort-draw order), importing nothing of the
program.  One round:

1. every cohort client runs ``R`` local SGD steps from the server params
   (a step whose mask is 0 leaves the params; the loss averages the masked
   steps), its update ``U_i = x - y_i``;
2. norms ``u_i = w_i ||U_i||``;
3. AOCS probabilities (Alg. 2: ``p = min(m u / sum u, 1)``, then up to
   ``j_max`` rescalings ``C = (m - n + I) / P`` of the unsaturated clients,
   the last applied when ``C <= 1``), a Bernoulli draw per client with the
   round's sampling key;
4. Eq. 2: ``G = sum_i mask_i w_i / p_i U_i`` in float32, and the server
   step ``x <- x - lr_global G`` in the parameter dtype.

Parameters live in their stated dtype; each local step computes in float32
from them and rounds back, as training without master weights does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12
# round k's key is fold_in(PRNGKey(seed), ROUND_FOLD + k) in the sim driver
ROUND_FOLD = 1000
# the initial params are made from fold_in(PRNGKey(seed), PARAMS_FOLD)
PARAMS_FOLD = 1


def aocs_probabilities(u, m: int, j_max: int):
    n = u.shape[0]
    p = jnp.minimum(m * u / jnp.maximum(jnp.sum(u), EPS), 1.0)
    p = jnp.where(u <= EPS, 0.0, p)
    done = jnp.asarray(False)
    for _ in range(j_max):
        unsat = p < 1.0
        count = jnp.sum(unsat)
        mass = jnp.sum(jnp.where(unsat, p, 0.0))
        c = (m - n + count) / jnp.maximum(mass, EPS)
        p = jnp.where(done, p, jnp.where(unsat, jnp.minimum(c * p, 1.0), p))
        done = done | (c <= 1.0)
    return p


def sampling_key(round_key):
    """The engines split the round key as ``(k_sample, k_comp)``."""
    return jax.random.split(round_key)[0]


def plan(u, weights, m: int, j_max: int, round_key):
    """``(probs, mask, scale)`` of one round from the norms."""
    p = aocs_probabilities(u, m, j_max)
    mask = jax.random.bernoulli(sampling_key(round_key), jnp.clip(p, 0.0, 1.0),
                                shape=u.shape)
    scale = jnp.where(mask & (p > EPS), weights / jnp.maximum(p, EPS), 0.0)
    return p, mask, scale


def local_update(loss_fn, params, batches, step_mask, lr: float):
    """``(U, loss)`` of one client: ``batches`` leaves are ``(R, ...)``."""
    dtypes = jax.tree_util.tree_map(lambda x: x.dtype, params)
    grad = jax.value_and_grad(loss_fn)

    def step(y, xs):
        b, m = xs
        loss, g = grad(jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), y), b)
        y = jax.tree_util.tree_map(
            lambda t, gt, dt: (t.astype(jnp.float32) - m * lr * gt).astype(dt),
            y, g, dtypes)
        return y, loss

    y, losses = jax.lax.scan(step, params, (batches, step_mask))
    upd = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), params, y)
    loss = jnp.sum(losses * step_mask) / jnp.maximum(jnp.sum(step_mask), 1.0)
    return upd, loss


def norms(updates, weights):
    """``w_i ||U_i||`` over a pytree of ``(n, ...)`` leaves."""
    sq = sum(jnp.sum(jnp.square(x).reshape(x.shape[0], -1), axis=1)
             for x in jax.tree_util.tree_leaves(updates))
    return weights * jnp.sqrt(sq)


def server_step(params, aggregate, lr_global: float):
    """``x - lr G`` with ``G`` rounded to the parameter dtype first, as the
    server applies it."""
    return jax.tree_util.tree_map(
        lambda p, g: (p.astype(jnp.float32)
                      - lr_global * g.astype(p.dtype).astype(jnp.float32)).astype(p.dtype),
        params, aggregate)


def replay_cohort(rng, sizes, n: int, steps: int, batch: int, local_epoch: bool = True):
    """One round's cohort and example rows, drawn from ``rng`` in the sim
    driver's order: the cohort without replacement, then one permutation
    of each client's examples, cycled into ``(steps, batch)``."""
    clients = rng.choice(len(sizes), size=n, replace=False)
    take = np.empty((n, steps, batch), np.int64)
    mask = np.empty((n, steps), np.float32)
    for i, c in enumerate(clients):
        size = int(sizes[c])
        k = max(1, min(steps, -(-size // batch))) if local_epoch else steps
        take[i] = np.resize(rng.permutation(size), (steps, batch))
        mask[i] = (np.arange(steps) < k).astype(np.float32)
    return clients, take, mask


def leaf_norms(tree) -> dict:
    """``{path: float64 norm}`` of every leaf, computed on the host."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(jax.device_get(v), np.float64).ravel())) for k, v in flat}


def tree_diff(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)
