"""The hybrid Mamba2/attention forecaster and the model interface of the
round engine, against the plain float32 reference (``hybrid_reference``)
at tiny widths on the CPU.

The reference steps the SSM one position at a time; the program runs the
chunked SSD.  Both draw their weights from the same seed as the
configuration states, so every comparison is of two independent
computations of the same numbers.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_reference as ref
from repro.configs.base import (FLConfig, ForecasterConfig,
                                HybridForecasterConfig, SSMConfig,
                                TransformConfig)
from repro.core import client, fedavg, losses, server_opt
from repro.data import synthetic, windows
from repro.models import hybrid_forecaster as hf
from repro.models import ssm

CFG = HybridForecasterConfig(
    layer_types=("mamba", "mamba", "attention", "mamba"), d_model=32,
    n_heads=4, n_kv_heads=2, head_dim=8, d_ff=48,
    ssm=SSMConfig(state_dim=8, head_dim=8, expand=2, conv_width=4,
                  chunk_size=8, n_groups=1),
    lookback=20, horizon=4)
# the rounds run a lighter stack: each layer kind once
SMALL = dataclasses.replace(CFG, layer_types=("mamba", "attention"))
LOSS = losses.make_loss("ew_mse", 2.0)
SEED = 7


def as_dict(cfg):
    d = dataclasses.asdict(cfg)
    d["layer_types"] = list(cfg.layer_types)
    return d


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def windows_():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.random((3, CFG.lookback + CFG.horizon)),
                       jnp.float32)


def _params(cfg=CFG):
    return cfg.init(jax.random.fold_in(jax.random.PRNGKey(SEED), 0))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def test_init_is_the_configuration_s_init():
    prog = _params()
    r = ref.init_params(SEED, as_dict(CFG))
    got = fl_norms(prog, jax.tree.map(jnp.zeros_like, prog))
    want = ref.change_norms(jax.tree.map(jnp.zeros_like, r), r)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6), k


def as_layers(params, cfg=CFG):
    """The program's tree in the reference's layout: the stacked segments
    as one dict per layer."""
    layers = []
    for (_, _, n), seg in zip(hf.segments(cfg), params["segments"]):
        layers += [jax.tree.map(lambda a: a[j], seg) for j in range(n)]
    return {**{k: v for k, v in params.items() if k != "segments"},
            "layers": layers}


def fl_norms(before, after, cfg=CFG):
    """The program's change norms by the reference's names."""
    return ref.change_norms(as_layers(before, cfg), as_layers(after, cfg))


@pytest.mark.parametrize("S,chunk", [(20, 8), (37, 16), (16, 16)])
def test_chunked_ssd_matches_the_recurrence(S, chunk):
    """The chunked SSD (a length that is not a multiple of the chunk
    included) against the per-step recurrence, with the published A and
    dt initialisation."""
    cfg = dataclasses.replace(CFG, ssm=dataclasses.replace(
        CFG.ssm, chunk_size=chunk))
    p = ssm.init_ssm(jax.random.PRNGKey(3), cfg.backbone)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, S, cfg.d_model))
    got, _ = ssm.ssm_forward(p, u, cfg.backbone)
    want = jax.jit(lambda p, u: ref._mamba(p, u, as_dict(cfg), jnp.float32,
                                           block=8))(p, u)
    assert _rel(got, want) < 1e-5


def test_mamba2_init_is_the_published_one():
    p = ssm.init_ssm(jax.random.PRNGKey(0), HybridForecasterConfig().backbone)
    a = np.exp(np.asarray(p["a_log"]))
    dt = np.log1p(np.exp(np.asarray(p["dt_bias"])))          # softplus
    assert p["a_log"].shape == (64,)
    assert a.min() >= 1.0 and a.max() <= 16.0
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() <= 0.1 * (1 + 1e-5)


def test_ssd_gradient_is_finite_over_a_long_chunk():
    """Large decays over a whole chunk: the masked upper triangle of the
    segment sums must not turn into a NaN gradient."""
    cfg = dataclasses.replace(CFG, ssm=dataclasses.replace(
        CFG.ssm, chunk_size=64))
    p = ssm.init_ssm(jax.random.PRNGKey(1), cfg.backbone)
    p = dict(p, a_log=jnp.full_like(p["a_log"], np.log(16.0)),
             dt_bias=jnp.full_like(p["dt_bias"], 3.0))
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 64, cfg.d_model))
    g = jax.grad(lambda q: jnp.sum(ssm.ssm_forward(q, u, cfg.backbone)[0]))(p)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))


@pytest.mark.parametrize("q_chunk", [256, 4])
def test_forward_matches_the_reference(windows_, q_chunk, monkeypatch):
    """Attention in one block of query rows, and in blocks of 4."""
    monkeypatch.setattr(hf, "ATTN_Q_CHUNK", q_chunk)
    x = windows_[:, :CFG.lookback]
    got = hf.forward(_params(), x, CFG)
    want = jax.jit(lambda p, x: ref.forward(p, x, as_dict(CFG), block=8,
                                            q_block=8))(
        ref.init_params(SEED, as_dict(CFG)), x)
    assert got.shape == (3, CFG.lookback, CFG.horizon)
    assert _rel(got, want) < 1e-5


def test_loss_and_gradients_match_the_reference(windows_):
    l, g = jax.value_and_grad(CFG.loss)(_params(), CFG.batch(windows_), LOSS)
    rl, rg = jax.jit(jax.value_and_grad(
        lambda p, w: ref.loss(p, w, as_dict(CFG), 2.0, block=8, q_block=8)))(
        ref.init_params(SEED, as_dict(CFG)), windows_)
    assert float(l) == pytest.approx(float(rl), rel=1e-6)
    got = fl_norms(jax.tree.map(jnp.zeros_like, g), g)
    want = ref.change_norms(jax.tree.map(jnp.zeros_like, rg), rg)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-4, abs=1e-9), k


def test_batch_targets_are_the_next_readings():
    w = jnp.arange(2 * 24, dtype=jnp.float32).reshape(2, 24)
    b = CFG.batch(w)
    assert b["x"].shape == (2, 20) and b["y"].shape == (2, 20, 4)
    np.testing.assert_array_equal(b["y"][1, 5], w[1, 6:10])


# ------------------------------------------------------------ FedAvg rounds
@pytest.fixture(scope="module")
def fleet():
    return np.asarray(synthetic.generate_buildings("CA", list(range(6)),
                                                   days=5))


@pytest.fixture
def client_loop(request, monkeypatch):
    """Steer the engine's client loop: "scan" by a device that holds
    nothing, "vmap" by one that states no memory; the jitted rounds are
    dropped so the next trace decides anew."""
    loop = request.param
    monkeypatch.setattr(fedavg, "_device_bytes",
                        lambda: 1 if loop == "scan" else None)
    jax.clear_caches()
    yield loop
    jax.clear_caches()


@pytest.mark.parametrize("client_loop", ["scan", "vmap"], indirect=True)
def test_two_fedavg_rounds_match_the_reference(fleet, client_loop):
    """Two rounds of run_federated_training -> pipeline_round ->
    local_update, K local steps of B windows, against the reference's
    rounds from the same seed."""
    fl = FLConfig(n_clients=6, clients_per_round=2, rounds=2, local_steps=3,
                  batch_size=4, lr=0.01, n_clusters=0, seed=SEED)
    prov = windows.ClientWindowProvider.from_series(
        fleet, SMALL.lookback, SMALL.horizon, train_frac=0.75, cache_size=0)
    assert fedavg.client_loop(_params(SMALL), 2) == client_loop
    res = fedavg.run_federated_training(prov, SMALL, fl)[-1]
    norms, rl = ref.fedavg_rounds(
        fleet, SEED, as_dict(SMALL),
        dict(clients_per_round=2, local_steps=3, batch_size=4, lr=0.01,
             beta=2.0, train_frac=0.75), 2, block=8, q_block=8)
    np.testing.assert_allclose(res.loss_history, rl, rtol=1e-5)
    got = fl_norms(_params(SMALL), res.params, SMALL)
    for k, v in norms[-1].items():
        assert got[k] == pytest.approx(v, rel=1e-3, abs=1e-7), k


@pytest.mark.parametrize("client_loop", ["scan"], indirect=True)
@pytest.mark.parametrize("tcfg", [
    TransformConfig(clip_norm=0.5, noise_multiplier=0.3),
    TransformConfig(quantize_bits=8),
], ids=["clip_noise", "quantize"])
def test_scan_loop_matches_vmap_with_a_transform_stack(fleet, client_loop,
                                                       tcfg, monkeypatch):
    """The clients one after another give the vmap path's round, the
    per-client transform stack included."""
    m, K, B = 3, 2, 4
    prov = windows.ClientWindowProvider.from_series(
        fleet, CFG.lookback, CFG.horizon, train_frac=0.75, cache_size=0)
    s, counts = prov.round_series(np.arange(m))
    bidx = jnp.asarray(np.random.default_rng(1).integers(
        0, int(counts.min()), (m, K, B)), jnp.int32)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(jax.random.PRNGKey(0),
                                                   jnp.arange(m))
    args = (_params(SMALL), jnp.asarray(s), None, bidx, jnp.asarray(counts),
            keys, jnp.float32(0.01), jnp.float32(0.0), SMALL, LOSS, tcfg)
    scan = fedavg.pipeline_round(*args)
    monkeypatch.setattr(fedavg, "_device_bytes", lambda: None)
    jax.clear_caches()
    vmap = fedavg.pipeline_round(*args)
    np.testing.assert_allclose(scan[1], vmap[1], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(scan[0]), jax.tree.leaves(vmap[0])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_client_loop_is_decided_by_the_device_s_memory(monkeypatch):
    params = {"w": jnp.zeros((1000,), jnp.float32)}         # 4,000 bytes
    monkeypatch.setattr(fedavg, "_device_bytes", lambda: 100_000)
    assert fedavg.client_loop(params, 8) == "vmap"          # 100,000 bytes
    assert fedavg.client_loop(params, 9) == "scan"          # 112,000 bytes
    monkeypatch.setattr(fedavg, "_device_bytes", lambda: None)
    assert fedavg.client_loop(params, 10 ** 6) == "vmap"


# ------------------------------------------------------- the LSTM unchanged
# loss histories and a parameter checksum of the paper's LSTM and GRU as
# the round engine gave them before it took a model spec (CPU, float64 hex)
LSTM_PINS = {
    "lstm": (["0x1.6eba380000000p-3", "0x1.de56c40000000p-5",
              "0x1.894c680000000p-5"], "-0x1.fad901f34e000p+2"),
    "gru": (["0x1.5d3c9e0000000p-4", "0x1.03dc5a0000000p-5",
             "0x1.0a90f20000000p-5"], "-0x1.96d4b13d2e000p+0"),
}


@pytest.mark.parametrize("cell", sorted(LSTM_PINS))
def test_recurrent_forecaster_round_bit_identical_through_the_spec(cell):
    series = synthetic.generate_buildings("CA", list(range(8)), days=10)
    fcfg = ForecasterConfig(cell=cell, hidden_dim=16)
    fl = FLConfig(n_clients=8, clients_per_round=4, rounds=3,
                  local_epochs=1, batch_size=32, lr=0.05, n_clusters=0,
                  seed=7)
    res = fedavg.run_federated_training(series, fcfg, fl)[-1]
    hist, checksum = LSTM_PINS[cell]
    assert [float(v).hex() for v in res.loss_history] == hist
    total = sum(np.asarray(a, np.float64).sum()
                for a in jax.tree.leaves(res.params))
    assert float(total).hex() == checksum


def test_sliced_minibatches_equal_prewindowed(monkeypatch):
    """Slicing each step's windows out of the series gives the pre-windowed
    round, bit for bit (the LSTM, forced onto the wide-window path)."""
    fcfg = ForecasterConfig(hidden_dim=8)
    assert client.prewindows(fcfg)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.random((2, 60)), jnp.float32)
    bidx = jnp.asarray(rng.integers(0, 49, (2, 3, 5)), jnp.int32)
    p = fcfg.init(jax.random.PRNGKey(0))
    run = lambda: fedavg.pipeline_round(
        p, x, None, bidx, jnp.ones(2), jnp.zeros((2, 2), jnp.uint32),
        jnp.float32(0.05), jnp.float32(0.0), fcfg, LOSS, TransformConfig())
    pre = run()
    monkeypatch.setattr(client, "PREWINDOW_MAX_WIDTH", 0)
    jax.clear_caches()
    assert not client.prewindows(fcfg)
    sliced = run()
    jax.clear_caches()
    for a, b in zip(jax.tree.leaves(pre), jax.tree.leaves(sliced)):
        np.testing.assert_array_equal(a, b)


def test_local_steps_replace_epochs():
    fl = FLConfig(local_steps=4, local_epochs=3)
    assert fl.client_opt.local_steps == 4
    with pytest.raises(ValueError):
        FLConfig(local_steps=-1)


def test_plain_fedavg_keeps_no_server_moment():
    p = {"w": jnp.ones((3,))}
    plain = server_opt.init_server_state(p, FLConfig())
    assert plain.m is None and plain.v is None
    momentum = server_opt.init_server_state(p, FLConfig(server_momentum=0.9))
    assert momentum.m["w"].shape == (3,) and momentum.v is None
    adam = server_opt.init_server_state(p, FLConfig(server_opt="fedadam"))
    assert adam.m["w"].shape == adam.v["w"].shape == (3,)
    new, _ = server_opt.server_update(p, {"w": jnp.zeros((3,))}, plain,
                                      FLConfig())
    np.testing.assert_array_equal(new["w"], 0.0)


def test_param_template_matches_init():
    t = CFG.param_template()
    p = _params()
    assert jax.tree.structure(t) == jax.tree.structure(p)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(t),
                                                   jax.tree.leaves(p)))
    assert CFG.num_params() == sum(a.size for a in jax.tree.leaves(p))
    assert HybridForecasterConfig().num_params() == 746_482_628
