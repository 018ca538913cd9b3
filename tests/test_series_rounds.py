"""The round's client data in series form (each client's normalized train
series, ``ClientWindowProvider.round_series``; ``y`` None) against the
window form (``round_batch``): the device slices the same windows under
the same minibatch indices, so every execution path gives the same
losses and parameters, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig, ForecasterConfig
from repro.core import fedavg, losses
from repro.core.client import local_update
from repro.data import partition, synthetic, windows
from repro.models import forecaster

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)
DAYS = [6, 9, 7, 10, 8, 6, 10, 9]        # ragged: the padding is real
M, B = len(DAYS), 16


@pytest.fixture(scope="module")
def fleet():
    return [synthetic.generate_buildings("CA", [i], days=d)[0]
            for i, d in enumerate(DAYS)]


@pytest.fixture(scope="module")
def cohort(fleet):
    """Both forms of one cohort of all M clients, and its minibatches."""
    prov = windows.ClientWindowProvider.from_series(
        fleet, FCFG.lookback, FCFG.horizon)
    ids = np.arange(M)
    x, y, counts = prov.round_batch(ids)
    s, _ = prov.round_series(ids)
    steps = partition.local_steps(prov.n_win_max, B, 1)
    bidx = partition.ragged_minibatch_indices(np.random.default_rng(7),
                                              counts, steps, B)
    return x, y, s, counts, bidx


def _tree_equal(a, b):
    jax.tree.map(np.testing.assert_array_equal, a, b)


@pytest.mark.parametrize("prox_mu", [0.0, 0.1])
def test_local_update_series_equals_windows(cohort, prox_mu):
    x, y, s, counts, bidx = cohort
    params = forecaster.init_forecaster(jax.random.PRNGKey(1), FCFG)
    loss = losses.make_loss("ew_mse", 2.0)
    lr, mu = jnp.float32(0.05), jnp.float32(prox_mu)
    for j in (int(np.argmin(counts)), int(np.argmax(counts))):
        p_w, l_w = local_update(params, x[j], y[j], bidx[j], lr, FCFG, loss,
                                prox_mu=mu)
        p_s, l_s = local_update(params, s[j], None, bidx[j], lr, FCFG, loss,
                                prox_mu=mu)
        assert float(l_s) == float(l_w)
        _tree_equal(p_s, p_w)


def _hier_regions():
    n = len(jax.devices())
    return 2 if n > 1 and n % 2 == 0 else 1


STRAGGLERS = dict(mode="semi_sync", buffer_k=5, stragglers="lognormal",
                  straggler_jitter=1.0)
ENGINES = {
    "sync": (dict(), None),
    "fedprox": (dict(server_opt="fedprox", prox_mu=0.1), None),
    "flat_mesh": (dict(), "flat"),
    "hierarchical": (dict(aggregation="hierarchical"), "hier"),
    "semi_sync": (STRAGGLERS, None),
    "semi_sync_mesh": (STRAGGLERS, "flat"),
    "secure": (dict(secure_agg=True), None),
    "secure_ring_hierarchical": (dict(secure_agg=True, quantize_bits=8,
                                      dp_clip=1.0,
                                      aggregation="hierarchical"), "hier"),
}


@pytest.mark.parametrize("name", list(ENGINES))
def test_engine_step_series_equals_windows(cohort, name):
    """Two rounds of ``RoundEngine.step`` per form, each on a fresh engine
    (semi-sync carries its straggler buffer from round to round); the
    mesh paths split the client axis of either form over all devices."""
    x, y, s, counts, bidx = cohort
    kw, mesh_kind = ENGINES[name]
    if mesh_kind == "hier":
        kw = dict(kw, n_regions=_hier_regions())
    flcfg = FLConfig(n_clients=M, clients_per_round=M, rounds=2,
                     n_clusters=0, batch_size=B, lr=0.05, loss="ew_mse",
                     seed=3, **kw)
    mesh = None if mesh_kind is None else \
        fedavg.aggregation_mod.make_mesh(flcfg)

    def run(*data):
        engine = fedavg.RoundEngine(FCFG, flcfg, mesh=mesh)
        params, state = engine.init(jax.random.PRNGKey(0))
        engine.attach_accountant(M, M)
        put = engine.put_clients(*(a for a in data if a is not None),
                                 bidx)
        args = (put[0], None, put[1]) if len(put) == 2 else put
        hist = []
        for t in range(2):
            params, state, l = engine.step(params, state, *args, counts,
                                           round_idx=t)
            hist.append(float(l))
        return hist, params, engine

    h_w, p_w, e_w = run(x, y)
    h_s, p_s, e_s = run(s, None)
    assert h_s == h_w
    _tree_equal(p_s, p_w)
    if name.startswith("semi_sync"):       # the buffered path really ran
        assert e_s.async_state.pending or e_s.async_state.late_folds
        assert len(e_s.async_state.pending) == len(e_w.async_state.pending)


def test_training_loss_history_equals_window_driven_loop(fleet):
    """``run_federated_training`` (series form) against the same round loop
    driven through ``round_batch`` and the window form."""
    flcfg = FLConfig(n_clients=M, clients_per_round=5, rounds=2,
                     n_clusters=0, batch_size=B, lr=0.05, loss="ew_mse",
                     seed=11)
    got = fedavg.run_federated_training(fleet, FCFG, flcfg)[-1]

    prov = windows.ClientWindowProvider.from_series(
        fleet, FCFG.lookback, FCFG.horizon)
    engine = fedavg.RoundEngine(FCFG, flcfg)
    holdout_rng, rng = fedavg._seed_rngs(flcfg.seed)
    members, _ = partition.holdout_clients(holdout_rng, M, flcfg.holdout_frac)
    counts = prov.train_counts.astype(np.float32)
    steps = partition.local_steps(prov.n_win_max, B, 1)
    params, state = engine.init(
        jax.random.fold_in(jax.random.PRNGKey(flcfg.seed), 0))
    engine.attach_accountant(len(members), 5)
    hist = []
    for t in range(flcfg.rounds):
        sel = engine.select(rng, members, 5, t, counts[members])
        bidx = partition.ragged_minibatch_indices(rng, counts[sel], steps, B)
        x, y, c = prov.round_batch(sel)
        params, state, l = engine.step(
            params, state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(bidx),
            c, round_idx=t)
        hist.append(float(l))
    np.testing.assert_array_equal(got.loss_history, hist)
    _tree_equal(got.params, params)
