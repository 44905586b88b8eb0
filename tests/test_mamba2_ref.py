"""The program's Mamba-2 (``models/ssm.py`` through ``build_model``) against
the plain reference ``tests/mamba2_ref.py``, on the CPU at the registry's
reduced size (``mamba2-130m-reduced``: 2 layers, d_model 128, 8 heads of
32, state 16, chunk 16, float32) with seeded random weights.

Both SSD paths are covered: the vectorised chunk path (64 tokens, 4
chunks) and the fused chunk scan that ``apply_mamba2`` takes past 64
chunks (1,040 tokens, 65 chunks).  The stress weights set every
``dt_bias`` to 0.5, so a chunk's ``sum dt |A|`` reaches a few hundred:
the masked upper triangle's ``exp`` then overflows, which made the
backward pass NaN while the forward pass stayed right.

Tolerances, from the gaps these tests read (largest over the cases):

- loss, relative 1e-6: read up to 8e-8, float32 rounding of sums taken in
  another order (chunked against quadratic);
- gradients, each leaf's relative L2 error 1e-4: read up to 1.8e-5, in
  ``A_log`` at the stress weights, whose gradient sums terms of both signs
  over every (t, s) pair; the other leaves read below 4e-6.  The same
  program in bfloat16 misses it by two orders (``test_bf16_program_fails``).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mamba2_ref
from repro.configs import get
from repro.configs.base import FLConfig
from repro.fl.engine import RoundEngine
from repro.models import build_model
from repro.models import ssm as S
from repro.models.layers import apply_norm

CFG = get("mamba2-130m-reduced")
LOSS_RTOL = 1e-6
GRAD_RTOL = 1e-4
# 4 chunks of 16 (vectorised path) and 65 (fused scan, nc > 64)
PATHS = {"vectorised": 64, "fused_scan": 1040}
STRESS_DT_BIAS = 0.5


@pytest.fixture(scope="module")
def model():
    return build_model(CFG)


def _params(model, stress: bool):
    params = model.init(jax.random.PRNGKey(0))
    if stress:
        mamba = params["layers"]["mamba"]
        mamba["dt_bias"] = jnp.full_like(mamba["dt_bias"], STRESS_DT_BIAS)
    return params


def _batch(seq: int, bsz: int = 1, seed: int = 1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (bsz, seq), 0,
                                CFG.vocab_size, jnp.int32)
    return {"tokens": tokens, "targets": tokens}


def _rel_errors(got, want) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_map(
            lambda a, b: float(np.linalg.norm(np.ravel(a - b)) / np.linalg.norm(np.ravel(b))),
            got, want))
    return {jax.tree_util.keystr(k): v for k, v in flat}


def _all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree_util.tree_leaves(tree))


def _max_chunk_decay(params, batch) -> float:
    """The largest ``sum dt |A|`` over one chunk of the first layer's input."""
    p = jax.tree_util.tree_map(lambda t: t[0], params["layers"])
    h = params["embed"]["embedding"][batch["tokens"]]
    x = apply_norm(p["norm"], h, CFG)
    _, heads, _ = S.dims(CFG)
    dt = (x @ p["mamba"]["in_proj"])[..., -heads:]
    dt = jax.nn.softplus(dt + p["mamba"]["dt_bias"])
    da = dt * jnp.exp(p["mamba"]["A_log"])
    seq = da.shape[1] - da.shape[1] % CFG.ssm_chunk
    chunks = da[:, :seq].reshape(da.shape[0], -1, CFG.ssm_chunk, heads)
    return float(jnp.max(jnp.sum(chunks, axis=2)))


@pytest.mark.parametrize("stress", [False, True], ids=["default", "stress"])
@pytest.mark.parametrize("path", list(PATHS))
def test_loss_and_grads_match_reference(model, path, stress):
    """Loss and every parameter's gradient of ``build_model(...).loss``
    equal the plain reference's; at the stress weights the gradients are
    finite too (the masked exponent)."""
    params = _params(model, stress)
    batch = _batch(PATHS[path])
    if stress:
        # the case the fix is for: the upper triangle's exp would overflow
        assert _max_chunk_decay(params, batch) > 88.8
    loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch)[0])(params)
    ref_loss, ref_grads = jax.value_and_grad(lambda p: mamba2_ref.loss(p, batch))(params)
    assert _all_finite(grads), "non-finite gradients"
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=LOSS_RTOL)
    errs = _rel_errors(grads, ref_grads)
    assert max(errs.values()) < GRAD_RTOL, errs


def test_bf16_program_fails(model):
    """The gradient tolerance separates: the program computing in bfloat16
    (the precision below the tested float32) misses it."""
    params = _params(model, stress=False)
    batch = _batch(PATHS["vectorised"])
    low = build_model(CFG.with_(dtype="bfloat16"))
    p16 = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), params)
    grads = jax.grad(lambda p: low.loss(p, batch)[0])(p16)
    ref_grads = jax.grad(lambda p: mamba2_ref.loss(p, batch))(params)
    errs = _rel_errors(jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), grads),
                       ref_grads)
    assert max(errs.values()) > GRAD_RTOL, errs


@pytest.mark.parametrize("path", list(PATHS))
def test_forward_unchanged_by_the_mask(monkeypatch, path):
    """Masking before ``exp`` changes no forward value: ``apply_mamba2``
    gives what the earlier ``where(tri, exp(seg), 0)`` gave, on inputs
    whose masked sums stay finite (the default ``dt_bias``)."""
    params = S.init_mamba2(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, PATHS[path], CFG.d_model))
    fixed = jax.jit(lambda p, x: S.apply_mamba2(p, x, CFG))(params, x)
    monkeypatch.setattr(S, "_masked_decay",
                        lambda seg, tri: jnp.where(tri, jnp.exp(seg), 0.0))
    before = jax.jit(lambda p, x: S.apply_mamba2(p, x, CFG))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(fixed), jax.tree_util.tree_leaves(before)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("path", list(PATHS))
def test_ssd_scopes_in_op_metadata(path):
    """The SSD's three stages are named in the compiled ops' metadata
    (``op_name``) on both paths, so any profile of the model can tell them
    apart."""
    params = S.init_mamba2(jax.random.PRNGKey(0), CFG)
    x = jnp.zeros((1, PATHS[path], CFG.d_model))
    hlo = jax.jit(lambda p, x: S.apply_mamba2(p, x, CFG)).lower(params, x).compile().as_text()
    for scope in ("ssd_intra", "ssd_states", "ssd_inter"):
        assert re.search(rf'op_name="[^"]*/{scope}/', hlo), scope


def test_scan_engine_round_norms(model):
    """One AOCS round of the scan engine with the Pallas aggregate (the
    benchmark's ``mamba2-scan`` round, at the reduced size and the stress
    weights): every client's update norm is finite and equals the
    reference's ``w_i ||U_i||``, ``U_i`` one float32 SGD step of the
    reference loss on that client's batch."""
    fl = FLConfig(n_clients=4, expected_clients=2, sampler="aocs", j_max=4,
                  local_steps=1, lr_local=0.05, round_engine="scan", scan_group=2,
                  cache_groups=2, agg_backend="pallas")
    params = _params(model, stress=True)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (fl.n_clients, 1, 2, 64), 0,
                                CFG.vocab_size, jnp.int32)
    weights = jnp.full((fl.n_clients,), 1.0 / fl.n_clients, jnp.float32)
    step = jax.jit(RoundEngine(model.loss, fl).make_step())
    _, _, m = step(params, (), {"tokens": tokens, "targets": tokens}, weights,
                   jax.random.PRNGKey(3), None, None)
    norms = np.asarray(m.norms)
    assert np.all(np.isfinite(norms)), norms

    def ref_norm(client_tokens):
        batch = {"tokens": client_tokens, "targets": client_tokens}
        g = jax.grad(lambda p: mamba2_ref.loss(p, batch))(params)
        upd = jax.tree_util.tree_map(lambda p, gp: p - (p - fl.lr_local * gp), params, g)
        return np.sqrt(sum(float(jnp.sum(u * u)) for u in jax.tree_util.tree_leaves(upd)))

    want = np.asarray(weights) * np.array([ref_norm(tokens[i, 0]) for i in range(fl.n_clients)])
    np.testing.assert_allclose(norms, want, rtol=GRAD_RTOL)
