"""The round loop's host spans and the round program's stage scopes.

``run_federated_training`` records one ``fl.round`` step span per round
holding its ``fl.step`` and ``fl.loss_sync``; between them, the next
round's ``fl.select``, ``fl.round_batch`` and ``fl.put``, prepared while the
device runs this one (``jax.profiler`` annotations, on the device trace's
clock); ``_pipeline_body`` names its stages with ``jax.named_scope``, which
reaches the compiled program's ``op_name`` metadata.  Read back here from a
real profiler trace on the CPU, at a tiny fleet."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig, ForecasterConfig, TransformConfig
from repro.core import fedavg, losses
from repro.data import partition, synthetic, windows
from repro.models import forecaster

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)
FLCFG = FLConfig(n_clients=8, clients_per_round=4, rounds=3, local_epochs=1,
                 batch_size=16, n_clusters=0, seed=5)
CHILDREN = ("fl.select", "fl.round_batch", "fl.put", "fl.step",
            "fl.loss_sync")
PREPARE = CHILDREN[:3]


@pytest.fixture(scope="module")
def series():
    return synthetic.generate_buildings("CA", list(range(8)), days=10)


@pytest.fixture(scope="module")
def traced(series, tmp_path_factory):
    """(loss history with no trace, loss history under a trace, the
    trace's ``fl.*`` host spans as (start, end, name, stats))."""
    from jax._src.profiler import ProfileData
    plain = fedavg.run_federated_training(series, FCFG, FLCFG)[-1]
    tdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(tdir):
        under = fedavg.run_federated_training(series, FCFG, FLCFG)[-1]
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
             for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:")
             for ln in pl.lines for e in ln.events
             if e.name.startswith("fl.")]
    return plain.loss_history, under.loss_history, spans


def test_one_round_span_per_round_each_holding_every_stage_once(traced):
    """Each stage appears once per round, and the preparing stages name
    the round they serve.  Round 0 prepares its own inputs; round t > 0's
    ``fl.select``, ``fl.round_batch`` and ``fl.put`` sit inside
    ``fl.round`` t-1, after its ``fl.step`` and before its
    ``fl.loss_sync``, with ``ahead`` 1; the last round prepares none."""
    _, _, spans = traced
    n = FLCFG.rounds
    rounds = sorted((s for s in spans if s[2] == "fl.round"),
                    key=lambda s: s[0])
    assert [r[3]["step_num"] for r in rounds] == list(range(n))
    for t, (s0, e0, _, _) in enumerate(rounds):
        inside = sorted((s for s in spans if s[2] != "fl.round"
                         and s0 <= s[0] and s[1] <= e0), key=lambda s: s[0])
        want = [(name, t) for name in PREPARE] if t == 0 else []
        want.append(("fl.step", None))
        if t + 1 < n:
            want += [(name, t + 1) for name in PREPARE]
        want.append(("fl.loss_sync", None))
        assert [(s[2], s[3].get("round")) for s in inside] == want
    assert len(spans) == n * (1 + len(CHILDREN))
    ahead = {s[3]["round"]: s[3]["ahead"] for s in spans
             if s[2] == "fl.select"}
    assert ahead == {t: int(t > 0) for t in range(n)}
    assert sum(ahead.values()) == n - 1


def test_put_span_counts_the_bytes_put(traced, series):
    """``fl.put``'s ``bytes`` is what lands on the device: the cohort's
    float32 normalized train series and the minibatch indices after JAX's
    int64 -> int32; ``fl.round_batch`` says it built series and how many
    bytes of them."""
    _, _, spans = traced
    prov = windows.ClientWindowProvider.from_series(
        series, FCFG.lookback, FCFG.horizon)
    m, n_win = FLCFG.clients_per_round, int(prov.n_win_max)
    steps = partition.local_steps(prov.n_win_max, FLCFG.batch_size,
                                  FLCFG.local_epochs)
    cut_max = n_win + FCFG.lookback + FCFG.horizon - 1
    series_bytes = 4 * m * cut_max
    want = series_bytes + 4 * m * steps * FLCFG.batch_size
    puts = [s[3] for s in spans if s[2] == "fl.put"]
    assert [p["bytes"] for p in puts] == [want] * FLCFG.rounds
    batches = [s[3] for s in spans if s[2] == "fl.round_batch"]
    assert len(batches) == FLCFG.rounds
    assert all(b["clients"] == m and b["windows"] > 0
               and b["layout"] == "series" and b["bytes"] == series_bytes
               for b in batches)


def test_loss_history_bit_identical_under_an_active_trace(traced):
    plain, under, _ = traced
    np.testing.assert_array_equal(plain, under)


@pytest.mark.parametrize("path,tcfg,scopes", [
    ("vmap", TransformConfig(), ("local_update", "aggregate")),
    ("vmap", TransformConfig(clip_norm=1.0, noise_multiplier=0.5),
     ("local_update", "transform", "aggregate")),
    ("mesh", TransformConfig(), ("local_update", "aggregate")),
], ids=["vmap_identity", "vmap_clip_noise", "mesh_identity"])
def test_round_program_names_its_stages(path, tcfg, scopes):
    """Each stage's scope reaches the compiled round's ``op_name``
    metadata, where a device trace reads it, on both execution paths."""
    m, n_win, steps, b = 4, 32, 2, 8
    x = jnp.zeros((m, n_win, FCFG.lookback, FCFG.input_dim), jnp.float32)
    y = jnp.zeros((m, n_win, FCFG.horizon), jnp.float32)
    bidx = jnp.zeros((m, steps, b), jnp.int32)
    w = jnp.ones((m,), jnp.float32)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(jax.random.PRNGKey(0),
                                                   jnp.arange(m))
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), FCFG)
    lr, mu, loss = jnp.float32(0.05), jnp.float32(0.0), losses.make_loss("mse")
    if path == "vmap":
        lowered = fedavg.pipeline_round.lower(
            params, x, y, bidx, w, keys, lr, mu, FCFG, loss, tcfg)
    else:
        mesh = jax.make_mesh((1,), ("clients",))
        lowered = fedavg.make_pipeline_round(mesh, FCFG, loss, tcfg).lower(
            params, x, y, bidx, w, keys, lr, mu)
    text = lowered.compile().as_text()
    for scope in scopes:
        assert f"/{scope}/" in text, scope
    if "transform" not in scopes:
        assert "/transform/" not in text


def test_step_span_names_its_client_loop_and_tokens(traced, series):
    """``fl.step`` says how the round trains its clients and how many
    positions it trains: clients x steps x B x look-back."""
    _, _, spans = traced
    prov = windows.ClientWindowProvider.from_series(
        series, FCFG.lookback, FCFG.horizon)
    steps = partition.local_steps(prov.n_win_max, FLCFG.batch_size,
                                  FLCFG.local_epochs)
    want = FLCFG.clients_per_round * steps * FLCFG.batch_size * FCFG.lookback
    stats = [s[3] for s in spans if s[2] == "fl.step"]
    assert [(s["client_loop"], s["tokens"]) for s in stats] == \
        [("vmap", want)] * FLCFG.rounds


def test_step_span_names_the_scan_recurrence_on_the_cpu(traced):
    """``fl.step`` says what differentiates the LSTM each round; off a
    TPU it is the scan (``forecaster.fused_recurrence``)."""
    _, _, spans = traced
    assert [s[3]["recurrence"] for s in spans if s[2] == "fl.step"] == \
        ["scan"] * FLCFG.rounds


def test_step_span_names_the_fused_recurrence_where_it_runs(
        series, tmp_path, monkeypatch):
    """Where the fused kernels differentiate the LSTM (here interpreted,
    the choice steered to them), every round's ``fl.step`` says so."""
    from jax._src.profiler import ProfileData
    monkeypatch.setattr(forecaster, "fused_recurrence", lambda c: True)
    flcfg = FLConfig(n_clients=8, clients_per_round=4, rounds=1,
                     local_epochs=1, batch_size=64, n_clusters=0, seed=5)
    jax.clear_caches()
    try:
        with jax.profiler.trace(str(tmp_path)):
            res = fedavg.run_federated_training(
                series, ForecasterConfig(), flcfg)[-1]
    finally:
        jax.clear_caches()
    assert np.all(np.isfinite(res.loss_history))
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    stats = [dict(e.stats) for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:") for ln in pl.lines
             for e in ln.events if e.name == "fl.step"]
    assert [s["recurrence"] for s in stats] == ["fused"] * flcfg.rounds


@pytest.mark.parametrize("loop", ["vmap", "scan"])
def test_hybrid_round_names_its_layers(loop, monkeypatch):
    """The hybrid backbone's scopes reach the compiled round's ``op_name``
    metadata inside ``local_update`` on both client loops: ``hybrid/mamba``
    holding ``ssd``, ``hybrid/attention`` and ``hybrid/mlp``."""
    from repro.configs.base import HybridForecasterConfig, SSMConfig
    cfg = HybridForecasterConfig(
        layer_types=("mamba", "attention"), d_model=16, n_heads=2,
        n_kv_heads=1, head_dim=8, d_ff=32,
        ssm=SSMConfig(state_dim=4, head_dim=8, expand=2, conv_width=4,
                      chunk_size=8, n_groups=1),
        lookback=16, horizon=4)
    monkeypatch.setattr(fedavg, "_device_bytes",
                        lambda: 1 if loop == "scan" else None)
    m = 2
    params = cfg.init(jax.random.PRNGKey(0))
    lowered = fedavg.pipeline_round.lower(
        params, jnp.zeros((m, 40), jnp.float32), None,
        jnp.zeros((m, 2, 3), jnp.int32), jnp.ones((m,), jnp.float32),
        jnp.zeros((m, 2), jnp.uint32), jnp.float32(0.01), jnp.float32(0.0),
        cfg, losses.make_loss("ew_mse", 2.0), TransformConfig())
    text = lowered.compile().as_text()
    for scope in ("/local_update/", "/hybrid/mamba/ssd/",
                  "/hybrid/attention/", "/hybrid/mlp/", "/aggregate/"):
        assert scope in text, scope
