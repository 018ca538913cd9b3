"""Parity of the fused LSTM sequence kernels on the federated round path.

``local_update`` differentiates through the forecaster; on a TPU a
one-layer LSTM's look-back is differentiated by the fused sequence kernels
(``kernels/lstm_seq.py``, chosen by ``forecaster.fused_recurrence``).
These tests run the kernels interpreted on the CPU, with the choice
steered to them, and pin them to value_and_grad of the jnp scan: the
kernels alone, a client's local update, and a mesh round.  Skips cleanly
where Pallas is unavailable.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("jax.experimental.pallas",
                    reason="Pallas not available in this jax build")

from repro.configs.base import ForecasterConfig
from repro.core import losses
from repro.kernels import lstm_seq, ops, platform
from repro.models import forecaster


# ------------------------------------------- the fused LSTM sequence kernels
# each gradient within 64 float32 ulps of its array's largest magnitude
F32_TOL = 64 * np.finfo(np.float32).eps
SEQ = dict(B=64, L=8, H=64)


def _seq_inputs(C, I=1, seed=0):
    rng = np.random.default_rng(seed)
    B, L, H = SEQ["B"], SEQ["L"], SEQ["H"]
    f = lambda *s, scale=1.0: jnp.asarray(rng.normal(size=s) * scale,
                                          jnp.float32)
    return (jnp.asarray(rng.random((C, B, L, I)), jnp.float32),
            f(C, I, 4 * H, scale=0.5), f(C, H, 4 * H, scale=H ** -0.5),
            f(C, 4 * H, scale=0.1), f(C, B, H))


def _scan_value_and_grad(x, wx, wh, b, dh):
    """h_L and the gradient of <h_L, dh> by value_and_grad of the jnp
    scan of ``forecaster.lstm_cell``, one client at a time."""
    def last_h(x, wx, wh, b):
        p = {"wx": wx, "wh": wh, "b": b}
        zeros = jnp.zeros((x.shape[0], wh.shape[0]), x.dtype)
        (h, _), _ = jax.lax.scan(
            lambda hc, xt: (forecaster.lstm_cell(xt, *hc, p), None),
            (zeros, zeros), x.swapaxes(0, 1))
        return h

    def one(x, wx, wh, b, dh):
        (_, h), g = jax.value_and_grad(
            lambda *a: (jnp.vdot(last_h(*a), dh), last_h(*a)),
            argnums=(0, 1, 2, 3), has_aux=True)(x, wx, wh, b)
        return h, g
    return jax.vmap(one)(x, wx, wh, b, dh)


def _close(got, want):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=F32_TOL * np.abs(want).max())


@pytest.mark.parametrize("call,C,I", [("direct", 5, 1), ("direct", 3, 2),
                                      ("vmap", 5, 1), ("nested_vmap", 6, 1)])
def test_lstm_seq_kernels_match_scan_value_and_grad(call, C, I, monkeypatch):
    """Interpreted kernels at H 64, L 8, B 64 against value_and_grad of
    the scan: h_L, dx, dwx, dwh, db.  Blocks of 2 clients, which divide
    none of these client counts; through ``ops`` the block comes from a
    vmap over clients (nested: a vmap of a vmap folds into one axis)."""
    x, wx, wh, b, dh = _seq_inputs(C, I)
    h_ref, g_ref = _scan_value_and_grad(x, wx, wh, b, dh)
    monkeypatch.setattr(lstm_seq, "MAX_BLOCK", 2)
    jax.clear_caches()                     # the block is read when traced
    fwd, bwd = lstm_seq.lstm_seq_fwd, lstm_seq.lstm_seq_bwd
    if call != "direct":
        fwd, bwd = jax.vmap(ops.lstm_seq_forward), \
            jax.vmap(ops.lstm_seq_backward)
    if call == "nested_vmap":
        split = lambda a: a.reshape((2, C // 2) + a.shape[1:])
        x, wx, wh, b, dh = map(split, (x, wx, wh, b, dh))
        fwd, bwd = jax.vmap(fwd), jax.vmap(bwd)
    h = fwd(x, wx, wh, b).reshape(h_ref.shape)
    g = [a.reshape(r.shape) for a, r in zip(bwd(x, wx, wh, b, dh), g_ref)]
    jax.clear_caches()
    assert lstm_seq.block_clients(C, SEQ["B"], SEQ["L"], SEQ["H"]) == 2
    _close(h, h_ref)
    for got, want in zip(g, g_ref):
        assert got.shape == want.shape
        _close(got, want)


@pytest.mark.parametrize("loop,prox_mu", [("vmap", 0.0), ("vmap", 0.1),
                                          ("single", 0.0)])
def test_local_update_fused_matches_scan(loop, prox_mu, monkeypatch):
    """Three SGD steps of ``client.local_update`` (EW-MSE, B 64) with the
    LSTM differentiated by the interpreted kernels against the scan: over
    5 vmapped clients, and one client alone (the scan client loop)."""
    from repro.core import client
    fcfg = ForecasterConfig()
    loss = losses.make_loss("ew_mse", 2.0)
    params = forecaster.init_forecaster(jax.random.PRNGKey(2), fcfg)
    rng = np.random.default_rng(3)
    series = jnp.asarray(rng.random((5, 300)), jnp.float32)
    bidx = jnp.asarray(rng.integers(0, 289, (5, 3, 64)), jnp.int32)

    def update():
        jax.clear_caches()                 # the path is chosen when traced
        f = functools.partial(client.local_update, y=None, cfg=fcfg,
                              loss=loss, prox_mu=jnp.float32(prox_mu))
        if loop == "single":
            return f(params, series[0], batch_idx=bidx[0],
                     lr=jnp.float32(0.05))
        return jax.vmap(lambda s, i: f(params, s, batch_idx=i,
                                       lr=jnp.float32(0.05)))(series, bidx)

    p_scan, l_scan = update()
    monkeypatch.setattr(forecaster, "fused_recurrence", lambda c: True)
    p_fused, l_fused = update()
    jax.clear_caches()
    np.testing.assert_allclose(np.asarray(l_fused), np.asarray(l_scan),
                               rtol=F32_TOL)
    jax.tree.map(_close, p_fused, p_scan)


@pytest.mark.parametrize("spec,tpu,want", [
    ("lstm", True, True),
    ("lstm", False, False),                  # the CPU
    ("gru", True, False),
    ("lstm2", True, False),                  # n_layers == 2
    ("hybrid", True, False),
    ("lstm32", True, False),                 # 2H is not whole lane tiles
])
def test_fused_recurrence_is_chosen_from_spec_and_platform(
        spec, tpu, want, monkeypatch):
    from repro.configs.base import HybridForecasterConfig
    cfg = {"lstm": ForecasterConfig(), "gru": ForecasterConfig(cell="gru"),
           "lstm2": ForecasterConfig(n_layers=2),
           "lstm32": ForecasterConfig(hidden_dim=32),
           "hybrid": HybridForecasterConfig()}[spec]
    if tpu:
        monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert forecaster.fused_recurrence(cfg) is want


def test_forecast_forward_is_the_scan_where_fused(monkeypatch):
    """Serving and evaluation run the forward alone: where the kernels
    would differentiate it, the forecast is still the scan's, bit for
    bit (the kernels run only under differentiation)."""
    fcfg = ForecasterConfig()
    params = forecaster.init_forecaster(jax.random.PRNGKey(4), fcfg)
    x = jnp.asarray(np.random.default_rng(4).random((64, 8, 1)), jnp.float32)
    jax.clear_caches()
    plain = forecaster.forecast(params, x, fcfg)
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    jax.clear_caches()
    fused = forecaster.forecast(params, x, fcfg)
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(plain))


@pytest.mark.parametrize("aggregation", ["flat", "hierarchical"])
def test_mesh_round_fused_matches_scan(aggregation, monkeypatch):
    """A ``RoundEngine`` round on a device mesh (a ``shard_map`` of the
    vmapped clients, as the four-chip hierarchical mix runs it): each
    device's clients fold into the kernels' client axis, and the round's
    loss and model match the scan's."""
    from repro.configs.base import FLConfig
    from repro.core import fedavg
    n_dev = len(jax.devices())
    M = 2 * n_dev
    flcfg = FLConfig(n_clients=M, clients_per_round=M, rounds=1,
                     n_clusters=0, batch_size=64, lr=0.05, loss="ew_mse",
                     aggregation=aggregation,
                     n_regions=2 if n_dev % 2 == 0 and n_dev > 1 else 1)
    rng = np.random.default_rng(5)
    series = rng.random((M, 200)).astype(np.float32)
    bidx = rng.integers(0, 189, (M, 2, 64)).astype(np.int32)
    counts = np.full(M, 189.0, np.float32)
    mesh = fedavg.aggregation_mod.make_mesh(flcfg)

    def round_():
        jax.clear_caches()                 # the path is chosen when traced
        engine = fedavg.RoundEngine(ForecasterConfig(), flcfg, mesh=mesh)
        params, state = engine.init(jax.random.PRNGKey(0))
        s, b = engine.put_clients(series, bidx)
        params, _, loss = engine.step(params, state, s, None, b, counts,
                                      round_idx=0)
        return params, float(loss)

    p_scan, l_scan = round_()
    monkeypatch.setattr(forecaster, "fused_recurrence", lambda c: True)
    p_fused, l_fused = round_()
    jax.clear_caches()
    np.testing.assert_allclose(l_fused, l_scan, rtol=F32_TOL)
    jax.tree.map(_close, p_fused, p_scan)
