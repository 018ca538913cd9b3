"""FedAvg engine invariants (paper Alg. 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig, ForecasterConfig, TransformConfig
from repro.core import fedavg, losses
from repro.core.client import local_update
from repro.data import synthetic, windows
from repro.models import forecaster


@pytest.fixture(scope="module")
def small_fl():
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    fcfg = ForecasterConfig(cell="lstm", hidden_dim=8)
    data = windows.batched_client_windows(series, fcfg.lookback, fcfg.horizon)
    return series, fcfg, data


def _uniform_round(params, x, y, bidx, lr, fcfg, loss):
    """One uniform-FedAvg round: the pipeline with equal weights and the
    identity transform stack."""
    m = x.shape[0]
    return fedavg.pipeline_round(
        params, x, y, bidx, jnp.ones((m,), jnp.float32),
        jnp.zeros((m, 2), jnp.uint32), lr, 0.0, fcfg, loss,
        TransformConfig())


def test_aggregate_is_mean():
    trees = {"a": jnp.arange(12.0).reshape(3, 4),
             "b": [jnp.ones((3, 2)), jnp.zeros((3,))]}
    sums, wsum = fedavg._weighted_sums(trees, jnp.ones((3,)))
    agg = jax.tree.map(lambda s: s / wsum, sums)
    np.testing.assert_allclose(agg["a"], jnp.arange(12.0).reshape(3, 4)
                               .mean(0))
    np.testing.assert_allclose(agg["b"][0], 1.0)


def test_single_client_round_equals_local_sgd(small_fl):
    """FedAvg with M=1 client must equal that client's plain local SGD."""
    series, fcfg, data = small_fl
    loss = losses.make_loss("mse")
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), fcfg)
    x = jnp.asarray(data["x_train"][:1])
    y = jnp.asarray(data["y_train"][:1])
    bidx = jnp.asarray(np.random.default_rng(0)
                       .integers(0, x.shape[1], size=(1, 5, 16)))
    p_fed, _ = _uniform_round(params, x, y, bidx, 0.01, fcfg, loss)
    p_loc, _ = local_update(params, x[0], y[0], bidx[0], 0.01, fcfg, loss)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 p_fed, p_loc)


def test_round_loss_decreases(small_fl):
    series, fcfg, data = small_fl
    loss = losses.make_loss("ew_mse", 2.0)
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), fcfg)
    x = jnp.asarray(data["x_train"])
    y = jnp.asarray(data["y_train"])
    r = np.random.default_rng(0)
    hist = []
    for t in range(8):
        bidx = jnp.asarray(r.integers(0, x.shape[1], size=(6, 10, 32)))
        params, l = _uniform_round(params, x, y, bidx, 0.05, fcfg, loss)
        hist.append(float(l))
    assert hist[-1] < hist[0]


def test_sharded_round_matches_vmap_round(small_fl):
    """shard_map execution (1-device mesh) == pseudo-distributed vmap."""
    series, fcfg, data = small_fl
    loss = losses.make_loss("mse")
    mesh = jax.make_mesh((1,), ("clients",))
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), fcfg)
    x = jnp.asarray(data["x_train"])
    y = jnp.asarray(data["y_train"])
    bidx = jnp.asarray(np.random.default_rng(0)
                       .integers(0, x.shape[1], size=(6, 4, 16)))
    p1, l1 = _uniform_round(params, x, y, bidx, 0.05, fcfg, loss)
    round_fn = fedavg.make_pipeline_round(mesh, fcfg, loss)
    p2, l2 = round_fn(params, x, y, bidx, jnp.ones((6,), jnp.float32),
                      jnp.zeros((6, 2), jnp.uint32), jnp.float32(0.05),
                      jnp.float32(0.0))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4,
                                                         atol=1e-6), p1, p2)


def test_run_federated_training_clusters(small_fl):
    series, fcfg, data = small_fl
    flcfg = FLConfig(n_clients=6, clients_per_round=3, rounds=2,
                     n_clusters=2, batch_size=16, cluster_days=10)
    out = fedavg.run_federated_training(series, fcfg, flcfg)
    assert set(out) == {0, 1}
    for res in out.values():
        assert res.loss_history.shape == (2,)
        assert np.isfinite(res.loss_history).all()
        assert res.cluster_assignments.shape == (6,)


def test_evaluate_global_metrics(small_fl):
    series, fcfg, data = small_fl
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), fcfg)
    x, y, stats = windows.flatten_test_windows(data)
    m = fedavg.evaluate_global(params, x, y, fcfg, stats=stats)
    assert 0.0 <= m["accuracy"] <= 100.0
    assert m["rmse"] >= 0.0
    assert m["per_horizon_accuracy"].shape == (fcfg.horizon,)
