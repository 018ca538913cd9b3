"""Streaming ClientWindowProvider (ISSUE 2 tentpole) + satellite regressions:
provider/materialized bit-equivalence (vmap AND shard_map), ragged
count-masking, mesh pad-up, round_robin seeding, rng decorrelation, and
jnp/np MAPE-epsilon parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import FLConfig, ForecasterConfig, TransformConfig
from repro.core import fedavg, losses, sampling
from repro.data import partition, synthetic, windows
from repro.data.windows import ClientWindowProvider
from repro.models import forecaster

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)
LOSS = losses.make_loss("mse")


@pytest.fixture(scope="module")
def equal_series():
    return synthetic.generate_buildings("CA", list(range(6)), days=16)


@pytest.fixture(scope="module")
def ragged_series():
    lens = [16, 11, 14, 16, 9, 12]
    return [synthetic.generate_buildings("CA", [i], days=d)[0]
            for i, d in enumerate(lens)]


# ------------------------------------------- provider == materialized
def test_round_batch_bit_identical_to_materialized(equal_series):
    prov = ClientWindowProvider.from_series(equal_series, FCFG.lookback,
                                            FCFG.horizon)
    data = windows.batched_client_windows(equal_series, FCFG.lookback,
                                          FCFG.horizon)
    ids = [4, 0, 2]
    x, y, counts = prov.round_batch(ids)
    np.testing.assert_array_equal(x, data["x_train"][ids])
    np.testing.assert_array_equal(y, data["y_train"][ids])
    np.testing.assert_array_equal(counts, [data["x_train"].shape[1]] * 3)
    xt, yt, _, (lo, hi) = prov.test_batch(ids)
    np.testing.assert_array_equal(xt, data["x_test"][ids])
    np.testing.assert_array_equal(yt, data["y_test"][ids])
    np.testing.assert_array_equal(lo, data["stats"][0][ids])
    np.testing.assert_array_equal(hi, data["stats"][1][ids])


@pytest.mark.parametrize("fleet", ["equal_series", "ragged_series"])
def test_round_series_windows_are_round_batch_windows(fleet, request):
    """Window k of client j sliced out of ``round_series`` is bit-for-bit
    ``round_batch``'s, for every valid k; rows past a client's cut are
    zero; the series is m * cut_max float32, L + H times under the
    windows."""
    prov = ClientWindowProvider.from_series(request.getfixturevalue(fleet),
                                            FCFG.lookback, FCFG.horizon)
    ids = [4, 0, 5, 1]
    x, y, counts = prov.round_batch(ids)
    s, s_counts = prov.round_series(ids)
    L, H = FCFG.lookback, FCFG.horizon
    cut_max = int(prov.n_win_max) + L + H - 1
    assert s.dtype == np.float32 and s.shape == (len(ids), cut_max)
    assert s.nbytes == len(ids) * cut_max * 4
    np.testing.assert_array_equal(s_counts, counts)
    k = np.arange(prov.n_win_max)
    for j, c in enumerate(counts.astype(int)):
        np.testing.assert_array_equal(
            s[j][k[:c, None] + np.arange(L)], x[j, :c, :, 0])
        np.testing.assert_array_equal(
            s[j][k[:c, None] + L + np.arange(H)], y[j, :c])
        assert (s[j, c + L + H - 1:] == 0).all()


def test_synthetic_provider_matches_in_memory(equal_series):
    """On-demand generator variant == wrapping the pre-generated array."""
    p_mem = ClientWindowProvider.from_series(equal_series, FCFG.lookback,
                                             FCFG.horizon)
    p_gen = ClientWindowProvider.from_synthetic("CA", range(6), FCFG.lookback,
                                                FCFG.horizon, days=16)
    x1, y1, c1 = p_mem.round_batch([5, 1])
    x2, y2, c2 = p_gen.round_batch([5, 1])
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(y1, y2)
    np.testing.assert_array_equal(c1, c2)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=3, deadline=None)
def test_streamed_training_bit_identical_vmap(seed):
    """Provider-fed engine round == materialized-tensor round (vmap path)."""
    series = synthetic.generate_buildings("CA", list(range(6)), days=16)
    data = windows.batched_client_windows(series, FCFG.lookback, FCFG.horizon)
    prov = ClientWindowProvider.from_series(series, FCFG.lookback,
                                            FCFG.horizon)
    rng = np.random.default_rng(seed)
    sel = rng.choice(6, size=4, replace=False)
    n_win = data["x_train"].shape[1]
    bidx = rng.integers(0, n_win, size=(4, 3, 16))
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), FCFG)
    lr, mu = jnp.float32(0.05), jnp.float32(0.0)
    w = jnp.full((4,), float(n_win), jnp.float32)
    keys = jnp.zeros((4, 2), jnp.uint32)
    x, y, _ = prov.round_batch(sel)
    p_s, l_s = fedavg.pipeline_round(
        params, jnp.asarray(x), jnp.asarray(y), jnp.asarray(bidx), w, keys,
        lr, mu, FCFG, LOSS, TransformConfig())
    p_m, l_m = fedavg.pipeline_round(
        params, jnp.asarray(data["x_train"][sel]),
        jnp.asarray(data["y_train"][sel]), jnp.asarray(bidx), w, keys, lr,
        mu, FCFG, LOSS, TransformConfig())
    assert float(l_s) == float(l_m)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), p_s, p_m)


def test_streamed_training_bit_identical_shard_map(equal_series):
    """Provider-fed round == materialized round through shard_map too."""
    data = windows.batched_client_windows(equal_series, FCFG.lookback,
                                          FCFG.horizon)
    prov = ClientWindowProvider.from_series(equal_series, FCFG.lookback,
                                            FCFG.horizon)
    n_dev = min(2, len(jax.devices()))
    mesh = jax.make_mesh((n_dev,), ("clients",))
    round_fn = fedavg.make_pipeline_round(mesh, FCFG, LOSS)
    sel = np.asarray([0, 3, 1, 5])
    n_win = data["x_train"].shape[1]
    bidx = np.random.default_rng(0).integers(0, n_win, size=(4, 3, 16))
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), FCFG)
    w = jnp.full((4,), float(n_win), jnp.float32)
    x, y, _ = prov.round_batch(sel)
    args = (jnp.asarray(bidx), w, jnp.zeros((4, 2), jnp.uint32),
            jnp.float32(0.05), jnp.float32(0.0))
    p_s, l_s = round_fn(params, jnp.asarray(x), jnp.asarray(y), *args)
    p_m, l_m = round_fn(params, jnp.asarray(data["x_train"][sel]),
                        jnp.asarray(data["y_train"][sel]), *args)
    assert float(l_s) == float(l_m)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), p_s, p_m)


def test_driver_array_and_provider_agree(equal_series):
    """run_federated_training(ndarray) == run_federated_training(provider)."""
    flcfg = FLConfig(n_clients=6, clients_per_round=3, rounds=2, n_clusters=2,
                     batch_size=16, cluster_days=8, lr=0.05)
    prov = ClientWindowProvider.from_synthetic("CA", range(6), FCFG.lookback,
                                               FCFG.horizon, days=16)
    out_a = fedavg.run_federated_training(equal_series, FCFG, flcfg)
    out_p = fedavg.run_federated_training(prov, FCFG, flcfg)
    assert set(out_a) == set(out_p)
    for cid in out_a:
        np.testing.assert_array_equal(out_a[cid].loss_history,
                                      out_p[cid].loss_history)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     out_a[cid].params, out_p[cid].params)


# ------------------------------------------------------- ragged histories
def test_ragged_counts_and_masking(ragged_series):
    prov = ClientWindowProvider.from_series(ragged_series, FCFG.lookback,
                                            FCFG.horizon)
    assert prov.train_counts.max() == prov.n_win_max
    assert len(set(prov.train_counts.tolist())) > 1
    x, y, counts = prov.round_batch([1, 4, 0])
    assert x.shape == (3, prov.n_win_max, FCFG.lookback, 1)
    for j, c in enumerate(counts.astype(int)):
        assert (x[j, c:] == 0).all() and (y[j, c:] == 0).all()
        assert (x[j, :c] != 0).any()


def test_ragged_minibatch_indices_respect_counts():
    rng = np.random.default_rng(0)
    counts = np.asarray([50, 7, 23])
    bidx = partition.ragged_minibatch_indices(rng, counts, 6, 32)
    assert bidx.shape == (3, 6, 32)
    for j, c in enumerate(counts):
        assert bidx[j].min() >= 0 and bidx[j].max() < c


def test_equal_count_indices_match_legacy_stream():
    """The fast path must reproduce the historical rng.integers draw."""
    a = partition.ragged_minibatch_indices(np.random.default_rng(3),
                                           np.full(4, 99), 5, 8)
    b = np.random.default_rng(3).integers(0, 99, size=(4, 5, 8))
    np.testing.assert_array_equal(a, b)


def test_ragged_training_and_streamed_eval(ragged_series):
    flcfg = FLConfig(n_clients=6, clients_per_round=4, rounds=2, n_clusters=0,
                     batch_size=16, lr=0.05, server_opt="fedavg_weighted",
                     sampling="weighted")
    prov = ClientWindowProvider.from_series(ragged_series, FCFG.lookback,
                                            FCFG.horizon)
    out = fedavg.run_federated_training(prov, FCFG, flcfg)[-1]
    assert np.isfinite(out.loss_history).all()
    m = fedavg.evaluate_unseen_clients(out.params, prov, FCFG, ids=[1, 4])
    assert 0.0 <= m["accuracy"] <= 100.0 and np.isfinite(m["rmse"])


def test_provider_rejects_too_short_history():
    with pytest.raises(ValueError):
        ClientWindowProvider.from_series(np.ones((2, 30), np.float32), 8, 4)


# ------------------------------------------------------- streamed eval parity
def test_streamed_eval_matches_materialized(equal_series):
    params = forecaster.init_forecaster(jax.random.PRNGKey(1), FCFG)
    data = windows.batched_client_windows(equal_series, FCFG.lookback,
                                          FCFG.horizon)
    x, y, stats = windows.flatten_test_windows(data)
    m_mat = fedavg.evaluate_global(params, x, y, FCFG, stats=stats)
    m_str = fedavg.evaluate_unseen_clients(params, equal_series, FCFG,
                                           clients_per_chunk=2)
    for k in ("rmse", "mape", "accuracy"):
        np.testing.assert_allclose(m_str[k], m_mat[k], rtol=1e-6)
    np.testing.assert_allclose(m_str["per_horizon_accuracy"],
                               m_mat["per_horizon_accuracy"], rtol=1e-6)


def test_mape_eps_parity_jnp_np(equal_series):
    """losses.mape (jnp) and evaluate_global (np) share ONE epsilon."""
    params = forecaster.init_forecaster(jax.random.PRNGKey(2), FCFG)
    data = windows.batched_client_windows(equal_series, FCFG.lookback,
                                          FCFG.horizon)
    x, y, _ = windows.flatten_test_windows(data)
    m = fedavg.evaluate_global(params, x, y, FCFG)    # normalized space
    pred = np.asarray(fedavg._predict(params, jnp.asarray(x), FCFG))
    np.testing.assert_allclose(m["mape"], float(losses.mape(pred, y)),
                               rtol=1e-5)
    np.testing.assert_allclose(m["accuracy"],
                               float(losses.accuracy(pred, y)), rtol=1e-5)


# ------------------------------------------------------- mesh pad-up fix
def test_mesh_pads_selection_up_not_down(equal_series):
    """10 configured clients on an 8-device mesh must train 10, not 8."""
    series = synthetic.generate_buildings("CA", list(range(12)), days=14)
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("clients",))
    flcfg = FLConfig(n_clients=12, clients_per_round=10, rounds=2,
                     n_clusters=0, batch_size=16, lr=0.05)
    out_m = fedavg.run_federated_training(series, FCFG, flcfg, mesh=mesh)[-1]
    out_v = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    # pad clients carry weight 0, so the padded mesh round == the exact
    # 10-client vmap round (up to psum reduction order)
    np.testing.assert_allclose(out_m.loss_history, out_v.loss_history,
                               rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4,
                                                         atol=1e-6),
                 out_m.params, out_v.params)


def test_uniform_step_masks_zero_weight_pads(equal_series):
    """weights==0 rows are excluded even under uniform aggregation."""
    data = windows.batched_client_windows(equal_series, FCFG.lookback,
                                          FCFG.horizon)
    x = jnp.asarray(data["x_train"][[0, 1, 0, 0]])   # rows 2,3 = pads
    y = jnp.asarray(data["y_train"][[0, 1, 0, 0]])
    bidx = jnp.asarray(np.random.default_rng(0)
                       .integers(0, x.shape[1], size=(4, 3, 16)))
    flcfg = FLConfig(n_clients=4, clients_per_round=4, rounds=1,
                     n_clusters=0, lr=0.05, server_opt="fedavg")
    eng = fedavg.RoundEngine(FCFG, flcfg, loss=LOSS)
    params, state = eng.init(jax.random.PRNGKey(0))
    w_pad = np.asarray([9.0, 9.0, 0.0, 0.0], np.float32)
    p_pad, _, l_pad = eng.step(params, state, x, y, bidx, w_pad)
    p_ref, _, l_ref = eng.step(params, state, x[:2], y[:2], bidx[:2],
                               np.asarray([9.0, 9.0], np.float32))
    np.testing.assert_allclose(float(l_pad), float(l_ref), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                         atol=1e-7),
                 p_pad, p_ref)


# ------------------------------------------------------- sampler + rng fixes
def test_round_robin_schedule_follows_config_seed():
    members = np.arange(30)
    rng = np.random.default_rng(0)
    s0 = sampling.make_sampler("round_robin", seed=0)
    s0b = sampling.make_sampler("round_robin", seed=0)
    s7 = sampling.make_sampler("round_robin", seed=7)
    np.testing.assert_array_equal(s0(rng, members, 5, 2),
                                  s0b(rng, members, 5, 2))
    assert not np.array_equal(s0(rng, members, 5, 2), s7(rng, members, 5, 2))
    # the per-round rng must NOT perturb the schedule
    np.testing.assert_array_equal(
        s0(np.random.default_rng(1), members, 5, 2),
        s0(np.random.default_rng(99), members, 5, 2))


def test_round_robin_exactly_m_when_oversubscribed():
    members = np.arange(4) + 50
    sel = sampling.round_robin_sampler(np.random.default_rng(0), members,
                                       10, 0, seed=3)
    assert len(sel) == 10 and set(sel) == set(members)


def test_holdout_rng_decorrelated_from_round_rng():
    hold, rnd = fedavg._seed_rngs(0)
    assert not np.array_equal(hold.permutation(64), rnd.permutation(64))
    # deterministic per seed
    h2, r2 = fedavg._seed_rngs(0)
    np.testing.assert_array_equal(fedavg._seed_rngs(0)[0].permutation(16),
                                  h2.permutation(16))
    assert not np.array_equal(h2.integers(0, 1 << 30, 8),
                              fedavg._seed_rngs(1)[0].integers(0, 1 << 30, 8))


def test_holdout_split_deterministic_through_driver(equal_series):
    flcfg = FLConfig(n_clients=6, clients_per_round=2, rounds=1, n_clusters=0,
                     batch_size=16, holdout_frac=0.34)
    a = fedavg.run_federated_training(equal_series, FCFG, flcfg)[-1]
    b = fedavg.run_federated_training(equal_series, FCFG, flcfg)[-1]
    np.testing.assert_array_equal(a.heldout_clients, b.heldout_clients)
    assert len(a.heldout_clients) == 2


# ------------------------------------------------------- clustering summary
def test_daily_summary_matches_daily_average_vector(equal_series):
    prov = ClientWindowProvider.from_series(equal_series, FCFG.lookback,
                                            FCFG.horizon)
    z_prov = prov.daily_summary(np.arange(6), days=10)
    z_mat = windows.daily_average_vector(equal_series, days=10)
    np.testing.assert_allclose(z_prov, z_mat, rtol=1e-6)


def test_daily_summary_pads_short_clients_train_period_only(ragged_series):
    """Short clients contribute only TRAIN days to z_k — the chronological
    test split must never inform cluster assignment."""
    prov = ClientWindowProvider.from_series(ragged_series, FCFG.lookback,
                                            FCFG.horizon)
    z = prov.daily_summary(np.arange(6), days=14)
    assert z.shape == (6, 14)
    assert np.isfinite(z).all()
    # client 4: 9-day history -> train cut = 6.75 days -> 6 whole train days
    d = int(prov._cuts[4]) // synthetic.STEPS_PER_DAY
    assert d == 6
    raw = np.asarray(ragged_series[4])
    np.testing.assert_allclose(
        z[4, :d], raw[:d * 96].reshape(d, 96).mean(-1), rtol=1e-6)
    np.testing.assert_allclose(z[4, d:], z[4, :d].mean(), rtol=1e-6)


def test_daily_summary_sub_day_train_period_is_finite():
    """A client whose train cut is < 1 day must yield a flat finite summary,
    not a NaN row that would poison k-means."""
    r = np.random.default_rng(0)
    series = [np.abs(r.normal(size=96)).astype(np.float32) + 1.0,   # cut = 72
              np.abs(r.normal(size=400)).astype(np.float32) + 1.0]
    prov = ClientWindowProvider.from_series(series, 8, 4)
    z = prov.daily_summary([0, 1], days=3)
    assert np.isfinite(z).all()
    np.testing.assert_allclose(z[0], series[0][:72].mean(), rtol=1e-6)


def test_evaluate_empty_ids_raises(equal_series):
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), FCFG)
    with pytest.raises(ValueError):
        fedavg.evaluate_unseen_clients(params, equal_series, FCFG, ids=[])


def test_driver_provider_caches_all_in_memory_clients(equal_series):
    """Array inputs get a full-population cache: full-participation rounds
    must not re-window every client every round through a tiny LRU."""
    prov = fedavg._as_provider(equal_series, FCFG)
    assert prov._cache_size == len(equal_series)
