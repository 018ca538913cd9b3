"""Loss-function properties (paper §3.3) — hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import losses

F = st.floats(-10, 10, allow_nan=False, width=32)


@given(st.integers(1, 8), st.integers(1, 64), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=30, deadline=None)
def test_ewmse_beta1_is_mse(h, n, seed):
    r = np.random.default_rng(seed)
    p = jnp.asarray(r.normal(size=(n, h)), jnp.float32)
    y = jnp.asarray(r.normal(size=(n, h)), jnp.float32)
    np.testing.assert_allclose(losses.ew_mse(p, y, beta=1.0),
                               losses.mse(p, y), rtol=1e-6)


@given(st.floats(1.0, 4.0), st.integers(2, 8), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=30, deadline=None)
def test_ewmse_weights_later_errors_more(beta, h, seed):
    """An error at the last horizon step costs >= the same error at step 0."""
    r = np.random.default_rng(seed)
    y = jnp.asarray(r.normal(size=(4, h)), jnp.float32)
    e = jnp.zeros((4, h)).at[:, 0].set(1.0)
    l_first = losses.ew_mse(y + e, y, beta)
    e = jnp.zeros((4, h)).at[:, -1].set(1.0)
    l_last = losses.ew_mse(y + e, y, beta)
    assert float(l_last) >= float(l_first) - 1e-6


def test_ewmse_matches_paper_formula():
    """EW-MSE = (1/N) Σ β^{i-1} (y_i - ŷ_i)² — checked against a loop."""
    r = np.random.default_rng(1)
    p, y = r.normal(size=(3, 4)), r.normal(size=(3, 4))
    beta = 2.0
    want = np.mean([[beta ** i * (p[b, i] - y[b, i]) ** 2 for i in range(4)]
                    for b in range(3)])
    got = float(losses.ew_mse(jnp.asarray(p, jnp.float32),
                              jnp.asarray(y, jnp.float32), beta))
    np.testing.assert_allclose(got, want, rtol=1e-5)


@given(st.integers(1, 6), st.integers(2, 32), st.integers(4, 40),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_weighted_ce_beta1_is_plain_ce(b, s, v, seed):
    r = np.random.default_rng(seed)
    logits = jnp.asarray(r.normal(size=(b, s, v)), jnp.float32)
    labels = jnp.asarray(r.integers(0, v, size=(b, s)), jnp.int32)
    got = losses.weighted_ce(logits, labels, beta=1.0)
    logp = jax.nn.log_softmax(logits, -1)
    want = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
    np.testing.assert_allclose(got, want, rtol=1e-5)


@given(st.sampled_from([1, 2, 4]), st.floats(1.0, 3.0),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_chunked_ce_matches_unchunked(nc, beta, seed):
    r = np.random.default_rng(seed)
    B, S, d, V = 2, 8 * nc, 16, 24
    h = jnp.asarray(r.normal(size=(B, S, d)), jnp.float32)
    w = jnp.asarray(r.normal(size=(d, V)) * 0.1, jnp.float32)
    labels = jnp.asarray(r.integers(0, V, size=(B, S)), jnp.int32)
    mask = jnp.asarray(r.integers(0, 2, size=(B, S)), bool)
    want = losses.weighted_ce(h @ w, labels, beta, mask)
    got = losses.chunked_weighted_ce(h, w, labels, beta, mask, chunk=S // nc)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_accuracy_is_100_minus_mape():
    r = np.random.default_rng(2)
    y = jnp.asarray(np.abs(r.normal(size=(100, 4))) + 1.0, jnp.float32)
    p = y * 1.1
    acc = float(losses.accuracy(p, y))
    mape = float(losses.mape(p, y))
    np.testing.assert_allclose(acc, 100.0 - mape, rtol=1e-5)
    np.testing.assert_allclose(mape, 10.0, rtol=1e-3)


def test_per_horizon_accuracy_shape():
    y = jnp.ones((50, 4)) * 2.0
    p = y.at[:, 3].mul(1.5)
    ph = losses.per_horizon_accuracy(p, y)
    assert ph.shape == (4,)
    np.testing.assert_allclose(ph[:3], 100.0, atol=1e-4)
    np.testing.assert_allclose(ph[3], 50.0, atol=1e-3)
