"""Property-based tests on model-layer invariants (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import ARCH_IDS, get_config
from repro.models import layers as L

sizes = st.sampled_from([8, 16, 32, 64])


@settings(max_examples=25, deadline=None)
@given(sizes, st.floats(1e3, 1e6))
def test_rope_preserves_norm(d, theta):
    """Rotations never change vector magnitude."""
    x = jax.random.normal(jax.random.PRNGKey(d), (2, 6, 4, d))
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    y = L.apply_rope(x, pos, theta)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-4)


def test_rope_is_relative():
    """<rope(q,i), rope(k,j)> depends only on i - j."""
    d = 32
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, d))

    def dot_at(i, j):
        qi = L.apply_rope(q, jnp.full((1, 1), i), 10_000.0)
        kj = L.apply_rope(k, jnp.full((1, 1), j), 10_000.0)
        return float(jnp.sum(qi * kj))
    assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)
    assert dot_at(5, 3) != pytest.approx(dot_at(5, 4), rel=1e-3)


@settings(max_examples=25, deadline=None)
@given(sizes, st.floats(-100, 100), st.floats(0.1, 100))
def test_rmsnorm_scale_invariant(d, shift, scale):
    """rmsnorm(c*x) == rmsnorm(x) for any positive c."""
    params = L.init_rmsnorm(d)
    x = jax.random.normal(jax.random.PRNGKey(d), (3, d)) + 0.1
    a = L.rmsnorm(params, x)
    b = L.rmsnorm(params, x * scale)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-3, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(st.floats(1.0, 100.0), st.floats(-1e4, 1e4))
def test_softcap_bounds(cap, v):
    out = float(L._softcap(jnp.float32(v), cap))
    assert abs(out) <= cap * 1.0001
    if abs(v) < cap / 10:            # near-linear region
        assert out == pytest.approx(v, rel=0.05, abs=1e-3)


def test_causal_mask_matches_window_infinite():
    m1 = L.causal_mask(16, 16)
    m2 = L.window_mask(16, 16, window=10**9)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


def test_attention_ignores_future_tokens():
    """Changing token t+1.. never changes output at t (causality)."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("phi3-medium-14b")
    p = L.init_attention(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(12), (1, 12))
    out1, _, _ = L.attention_fwd(cfg, p, x, pos, is_global=True)
    x2 = x.at[:, 8:].set(jax.random.normal(jax.random.PRNGKey(2),
                                           (1, 4, cfg.d_model)))
    out2, _, _ = L.attention_fwd(cfg, p, x2, pos, is_global=True)
    np.testing.assert_allclose(np.asarray(out1[:, :8]),
                               np.asarray(out2[:, :8]), atol=1e-5)


def test_sliding_window_forgets_distant_tokens():
    """With window W, output at t is independent of tokens < t - W."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("gemma2-9b").replace(local_window=4,
                                                use_qk_norm=False)
    p = L.init_attention(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(12), (1, 12))
    out1, _, _ = L.attention_fwd(cfg, p, x, pos, is_global=False)
    x2 = x.at[:, :4].set(0.0)     # mutate tokens far outside the window
    out2, _, _ = L.attention_fwd(cfg, p, x2, pos, is_global=False)
    np.testing.assert_allclose(np.asarray(out1[:, 9:]),
                               np.asarray(out2[:, 9:]), atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_param_count_formula_matches_init(seed):
    """Analytic param_count tracks actual init within 5% (smoke sizes)."""
    from repro.configs import get_smoke_config
    from repro.models import model as M
    arch = ARCH_IDS[seed % len(ARCH_IDS)]
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    actual = M.count_params(params)
    analytic = cfg.param_count()
    assert abs(actual - analytic) / actual < 0.25  # norms/bias slack
