"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gru_cell import gru_cell
from repro.kernels.lstm_cell import lstm_cell

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("B,I,H,bb,bh", [
    (8, 1, 16, 8, 16), (64, 8, 64, 32, 32), (128, 4, 128, 128, 128),
    (32, 16, 256, 16, 64),
])
def test_lstm_cell_sweep(B, I, H, bb, bh, dt):
    r = np.random.default_rng(B + I + H)
    x = jnp.asarray(r.normal(size=(B, I)), dt)
    h = jnp.asarray(r.normal(size=(B, H)), dt)
    c = jnp.asarray(r.normal(size=(B, H)), dt)
    wx = jnp.asarray(r.normal(size=(I, 4 * H)) * 0.2, dt)
    wh = jnp.asarray(r.normal(size=(H, 4 * H)) * 0.2, dt)
    b = jnp.asarray(r.normal(size=(4 * H,)) * 0.2, dt)
    h1, c1 = lstm_cell(x, h, c, wx, wh, b, block_b=bb, block_h=bh,
                       interpret=True)
    h2, c2 = ref.lstm_cell_ref(x, h, c, wx, wh, b)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), **_tol(dt))
    np.testing.assert_allclose(np.asarray(c1, np.float32),
                               np.asarray(c2, np.float32), **_tol(dt))


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("B,I,H,bb,bh", [
    (8, 1, 16, 8, 16), (64, 8, 64, 32, 32), (128, 4, 128, 128, 128),
])
def test_gru_cell_sweep(B, I, H, bb, bh, dt):
    r = np.random.default_rng(B + I + H + 1)
    x = jnp.asarray(r.normal(size=(B, I)), dt)
    h = jnp.asarray(r.normal(size=(B, H)), dt)
    wx = jnp.asarray(r.normal(size=(I, 3 * H)) * 0.2, dt)
    wh = jnp.asarray(r.normal(size=(H, 3 * H)) * 0.2, dt)
    b = jnp.asarray(r.normal(size=(3 * H,)) * 0.2, dt)
    h1 = gru_cell(x, h, wx, wh, b, block_b=bb, block_h=bh, interpret=True)
    h2 = ref.gru_cell_ref(x, h, wx, wh, b)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), **_tol(dt))


@pytest.mark.parametrize("dt", [jnp.float32])
@pytest.mark.parametrize("B,S,Hq,Hkv,hd,win,bq,bk", [
    (2, 128, 4, 4, 32, 0, 64, 64),          # MHA
    (2, 256, 8, 2, 64, 0, 128, 128),        # GQA 4:1
    (1, 256, 4, 1, 64, 0, 128, 64),         # MQA
    (1, 512, 2, 2, 32, 128, 128, 128),      # sliding window
    (3, 384, 6, 2, 16, 0, 128, 128),        # odd head count / small hd
])
def test_flash_attention_sweep(B, S, Hq, Hkv, hd, win, bq, bk, dt):
    r = np.random.default_rng(S + Hq)
    q = jnp.asarray(r.normal(size=(B, S, Hq, hd)), dt)
    k = jnp.asarray(r.normal(size=(B, S, Hkv, hd)), dt)
    v = jnp.asarray(r.normal(size=(B, S, Hkv, hd)), dt)
    o1 = flash_attention(q, k, v, window=win, block_q=bq, block_k=bk,
                         interpret=True)
    o2 = ref.flash_attention_ref(q, k, v, window=win)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    r = np.random.default_rng(7)
    q = jnp.asarray(r.normal(size=(2, 256, 4, 64)), jnp.bfloat16)
    k = jnp.asarray(r.normal(size=(2, 256, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(r.normal(size=(2, 256, 2, 64)), jnp.bfloat16)
    o1 = flash_attention(q, k, v, interpret=True)
    o2 = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=3e-2, atol=3e-2)


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([16, 32, 64]),
       st.sampled_from([8, 16, 64]))
@settings(max_examples=10, deadline=None)
def test_lstm_cell_property(seed, H, B):
    """Fused cell == oracle for random shapes (property sweep)."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(B, 4)), jnp.float32)
    h = jnp.asarray(r.normal(size=(B, H)), jnp.float32)
    c = jnp.asarray(r.normal(size=(B, H)), jnp.float32)
    p = {"wx": jnp.asarray(r.normal(size=(4, 4 * H)) * 0.3, jnp.float32),
         "wh": jnp.asarray(r.normal(size=(H, 4 * H)) * 0.3, jnp.float32),
         "b": jnp.asarray(r.normal(size=(4 * H,)) * 0.3, jnp.float32)}
    h1, c1 = ops.lstm_cell_fused(x, h, c, p)
    h2, c2 = ref.lstm_cell_ref(x, h, c, p["wx"], p["wh"], p["b"])
    np.testing.assert_allclose(h1, h2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c1, c2, rtol=1e-5, atol=1e-5)


def test_forecaster_pallas_path_matches_jnp():
    """cell_impl='pallas' gives the same forecasts as the jnp path."""
    from repro.configs.base import ForecasterConfig
    from repro.models import forecaster
    r = np.random.default_rng(0)
    for cell in ("lstm", "gru"):
        cfg = ForecasterConfig(cell=cell, hidden_dim=32)
        params = forecaster.init_forecaster(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(r.normal(size=(16, cfg.lookback, 1)), jnp.float32)
        y1 = forecaster.forecast(params, x, cfg, "jnp")
        y2 = forecaster.forecast(params, x, cfg, "pallas")
        np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)


def test_model_flash_path_matches_jnp():
    """USE_FLASH_KERNEL routes full-sequence attention through the Pallas
    kernel (interpret mode) — model outputs must match the jnp path."""
    import numpy as _np
    from repro.configs import get_config
    from repro.models import attention as attn_mod
    from repro.models import transformer as tfm
    cfg = get_config("qwen2-72b").reduced()
    params = tfm.init_model(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(_np.random.default_rng(0)
                       .integers(0, cfg.vocab_size, (1, 128)), jnp.int32)
    l_ref, _, _ = tfm.forward(params, {"tokens": toks}, cfg,
                              dtype=jnp.float32, remat=False)
    attn_mod.USE_FLASH_KERNEL = True
    try:
        l_flash, _, _ = tfm.forward(params, {"tokens": toks}, cfg,
                                    dtype=jnp.float32, remat=False)
    finally:
        attn_mod.USE_FLASH_KERNEL = False
    np.testing.assert_allclose(np.asarray(l_flash), np.asarray(l_ref),
                               rtol=2e-4, atol=2e-4)
