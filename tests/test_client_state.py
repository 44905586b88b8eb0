"""Hypothesis property suite for the client-state layer (sim/pool.py) and
its AvailabilityTrace coupling into ``core/ocs.py::sampling_plan``.

Properties (all seeded, ``deadline=None`` so CI stays deterministic):

* the Markov chain initialised at stationarity keeps its marginal:
  after any one step the empirical up-fraction matches
  ``pi = p_up / (p_up + p_down)`` — and in the degenerate Appendix-E case
  ``p_up = q, p_down = 1 - q`` the transition ignores the current state
  *bitwise*, recovering the i.i.d. Bernoulli(q) availability model exactly;
* ``step_client_state`` is deterministic in the round key: the same key
  reproduces the trace bit-for-bit, a different key does not;
* a trace-driven plan still satisfies the Eq. 7 budget — ``sum(p) = m``
  whenever at least m *up* clients have non-zero norm — and the Eq. 4 scale
  identity ``scale_i = mask_i * w_i / (p_i * include_prob_i)`` exactly;
* fixed-key Monte-Carlo unbiasedness over the WHOLE system process (chain
  state x deadline x dropout x Bernoulli sampling): ``E[scale_i] -> w_i``,
  the property that makes the straggler scenarios' estimator honest.

Guarded like tests/test_sampling_plan.py: without hypothesis only the
property tests skip — the deterministic tests below still run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, seed, settings, strategies as st
except ImportError:
    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

    def settings(*a, **k):
        return lambda f: f

    def seed(*a, **k):
        return lambda f: f

    def given(*a, **k):
        return pytest.mark.skip(reason="property tests need hypothesis")

from repro.configs.base import FLConfig
from repro.core import ocs
from repro.sim.pool import SystemConfig, init_client_state, step_client_state

_EPS = 1e-12

# bounds exact in float32: a width-32 strategy refuses a bound (0.05, 0.95)
# that float32 cannot represent
probs_01 = st.floats(min_value=0.0625, max_value=0.9375, allow_nan=False, width=32)
norm_vectors = st.lists(
    st.floats(min_value=0.0, max_value=1e4, allow_nan=False, width=32),
    min_size=4,
    max_size=32,
)


def _full_trace(cfg, n, key):
    """One driver-shaped state step over the full pool: init at stationarity
    from ``fold_in(key, 2)``, then step keyed on ``key`` itself."""
    state = init_client_state(n, cfg, jax.random.fold_in(key, 2))
    return step_client_state(state, key, jnp.arange(n), cfg)


# --- chain marginals ------------------------------------------------------

@seed(20260801)
@settings(max_examples=25, deadline=None)
@given(probs_01, probs_01, st.integers(min_value=0, max_value=1 << 20))
def test_chain_preserves_stationary_marginal(p_up, p_down, key_int):
    """Initialised at ``pi = p_up/(p_up+p_down)``, one chain step keeps the
    up-fraction at pi (stationarity — the property that makes include_prob's
    ``pi`` factor the true per-round availability marginal)."""
    cfg = SystemConfig(p_up=p_up, p_down=p_down)
    n = 4096
    state, trace = _full_trace(cfg, n, jax.random.PRNGKey(key_int))
    pi = cfg.stationary()
    tol = 4.0 * np.sqrt(pi * (1 - pi) / n) + 1e-3
    assert abs(float(jnp.mean(state.up)) - pi) < tol
    assert abs(float(jnp.mean(trace.up)) - pi) < tol


@seed(20260802)
@settings(max_examples=25, deadline=None)
@given(probs_01, st.integers(min_value=0, max_value=1 << 20))
def test_degenerate_chain_is_bernoulli_q_bitwise(q, key_int):
    """Appendix-E recovery: with ``p_up = q, p_down = 1 - q`` the transition
    thresholds coincide, so the next state is the same i.i.d. Bernoulli(q)
    draw from EVERY current state — bitwise, not just in distribution."""
    cfg = SystemConfig(p_up=q, p_down=1.0 - q)
    n = 512
    key = jax.random.PRNGKey(key_int)
    lat = jnp.ones((n,), jnp.float32)
    from repro.sim.pool import ClientState

    all_up = ClientState(up=jnp.ones((n,), bool), lat_scale=lat)
    all_down = ClientState(up=jnp.zeros((n,), bool), lat_scale=lat)
    s_up, t_up = step_client_state(all_up, key, jnp.arange(n), cfg)
    s_dn, t_dn = step_client_state(all_down, key, jnp.arange(n), cfg)
    assert np.array_equal(np.asarray(s_up.up), np.asarray(s_dn.up))
    assert np.array_equal(np.asarray(t_up.up), np.asarray(t_dn.up))
    # and the marginal is q
    pi = cfg.stationary()
    assert pi == pytest.approx(q, abs=1e-6)
    np.testing.assert_allclose(np.asarray(t_up.include_prob), pi, atol=1e-6)


@seed(20260803)
@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=1 << 20),
       st.integers(min_value=0, max_value=1 << 20))
def test_state_step_deterministic_in_round_key(ka, kb):
    """Same round key => bit-identical trace AND next state; the trace is a
    pure function of (state, round_key) — what makes the three driver modes
    (and a crash-recovery replay) agree bitwise."""
    cfg = SystemConfig(p_up=0.4, p_down=0.3, latency_sigma=0.6, deadline=2.0,
                       drop_prob=0.2)
    n = 64
    state = init_client_state(n, cfg, jax.random.PRNGKey(0))
    sa, ta = step_client_state(state, jax.random.PRNGKey(ka), jnp.arange(n), cfg)
    sa2, ta2 = step_client_state(state, jax.random.PRNGKey(ka), jnp.arange(n), cfg)
    for x, y in zip(jax.tree_util.tree_leaves((sa, ta)),
                    jax.tree_util.tree_leaves((sa2, ta2))):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    if ka != kb:
        _, tb = step_client_state(state, jax.random.PRNGKey(kb),
                                  jnp.arange(n), cfg)
        diff = any(
            not np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree_util.tree_leaves(ta),
                            jax.tree_util.tree_leaves(tb))
        )
        assert diff, "distinct round keys drew identical traces"


# --- trace-driven plans ---------------------------------------------------

@seed(20260804)
@settings(max_examples=60, deadline=None)
@given(norm_vectors, st.integers(min_value=0, max_value=1 << 20))
def test_trace_plan_budget_and_scale_identity(u_list, key_int):
    """Eq. 7 budget and Eq. 4 scale identity survive the trace path:
    ``sum(p) = m`` whenever >= m up clients have non-zero norm, and
    ``scale_i = mask_i * w_i / (p_i * include_prob_i)`` exactly."""
    n = len(u_list)
    u = jnp.asarray(u_list, jnp.float32)
    w = jnp.full((n,), 1.0 / n)
    m = max(1, n // 3)
    cfg = SystemConfig(p_up=0.7, p_down=0.3, latency_sigma=0.5, deadline=2.5,
                       drop_prob=0.15)
    key = jax.random.PRNGKey(key_int)
    _, trace = _full_trace(cfg, n, key)
    plan = ocs.sampling_plan(u, w, m, key, sampler="optimal",
                             availability=trace)
    p, mask, sel = map(np.asarray, (plan.probs, plan.mask, plan.selected))
    up, on_time, kept = map(np.asarray, (trace.up, trace.on_time, trace.kept))
    q = np.asarray(trace.include_prob)
    assert np.all(p >= -1e-6) and np.all(p <= 1 + 1e-6)
    assert np.all(p[~up] == 0.0)           # down clients can never be drawn
    assert not np.any(sel & ~up)           # selected subset of up
    assert not np.any(mask & ~(sel & on_time & kept))
    if ((np.asarray(u) > _EPS) & up).sum() >= m:
        assert float(plan.expected_clients) == pytest.approx(m, rel=2e-3)
    want = np.where(mask & (p > _EPS),
                    np.asarray(w) / np.maximum(p * q, _EPS), 0.0)
    np.testing.assert_allclose(np.asarray(plan.scale), want,
                               rtol=1e-6, atol=1e-7)


def test_trace_plan_monte_carlo_unbiased():
    """Fixed-key Monte-Carlo over the WHOLE system process: chain state at
    stationarity, deadline misses, dropout faults and the Bernoulli draw —
    ``E[scale_i] -> w_i`` still (the generalized Eq. 4 unbiasedness the
    include_prob rescaling buys)."""
    n, m = 6, 3
    u = jnp.asarray([1.0, 2.0, 0.5, 4.0, 1.5, 3.0], jnp.float32)
    w = jnp.full((n,), 1.0 / n)
    cfg = SystemConfig(p_up=0.75, p_down=0.25, latency_sigma=0.4, deadline=3.0,
                       drop_prob=0.1)

    def draw(key):
        _, trace = _full_trace(cfg, n, key)
        return ocs.sampling_plan(u, w, m, key, sampler="optimal",
                                 availability=trace).scale

    draws = jax.vmap(draw)(jax.random.split(jax.random.PRNGKey(0), 6000))
    mean = np.asarray(draws).mean(0)
    np.testing.assert_allclose(mean, np.asarray(w), rtol=0.12)


def test_trace_scalar_q_equivalence_is_exact_at_stationarity():
    """The degenerate trace (no deadline, no dropout, Bernoulli(q) chain)
    carries ``include_prob == q`` everywhere — the Appendix-E scalar path's
    rescale factor, so the estimator algebra coincides."""
    cfg = SystemConfig(p_up=0.7, p_down=0.3)
    _, trace = _full_trace(cfg, 32, jax.random.PRNGKey(5))
    np.testing.assert_allclose(np.asarray(trace.include_prob), 0.7, atol=1e-6)
    assert bool(jnp.all(trace.on_time)) and bool(jnp.all(trace.kept))


# --- config plumbing ------------------------------------------------------

def test_system_config_validation():
    with pytest.raises(ValueError, match="p_up"):
        SystemConfig(p_up=1.5)
    with pytest.raises(ValueError, match="drop_prob"):
        SystemConfig(drop_prob=1.0)
    with pytest.raises(ValueError, match="deadline"):
        SystemConfig(deadline=0.0)
    with pytest.raises(ValueError, match="latency_sigma"):
        SystemConfig(latency_sigma=-0.1)


def test_cohort_target_over_selection():
    """over_select widens the Eq. 7 budget (sample > m, keep the survivors);
    the default 1.0 bit-preserves the original target."""
    fl = FLConfig(n_clients=16, expected_clients=4)
    assert fl.cohort_target() == 4
    assert FLConfig(n_clients=16, expected_clients=4,
                    over_select=1.5).cohort_target() == 6
    assert FLConfig(n_clients=16, expected_clients=12,
                    over_select=2.0).cohort_target() == 16  # capped at n
    with pytest.raises(ValueError, match="over_select"):
        FLConfig(n_clients=16, expected_clients=4, over_select=0.5)
