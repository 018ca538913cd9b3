"""Round-engine properties (ISSUE 1 tentpole): aggregation weighting, server
optimizers, FedProx, client sampling, and vmap/shard_map path equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import FLConfig, ForecasterConfig
from repro.core import fedavg, losses, sampling, server_opt
from repro.core.client import local_update
from repro.data import partition, synthetic, windows
from repro.models import forecaster

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)
LOSS = losses.make_loss("mse")              # one object -> one jit cache entry
MESH = jax.make_mesh((1,), ("clients",))


def tree_close(a, b, rtol=1e-5, atol=1e-6):
    jax.tree.map(lambda u, v: np.testing.assert_allclose(u, v, rtol=rtol,
                                                         atol=atol), a, b)


@pytest.fixture(scope="module")
def fl_data():
    series = synthetic.generate_buildings("CA", list(range(4)), days=12)
    data = windows.batched_client_windows(series, FCFG.lookback, FCFG.horizon)
    x = jnp.asarray(data["x_train"])
    y = jnp.asarray(data["y_train"])
    bidx = jnp.asarray(np.random.default_rng(0)
                       .integers(0, x.shape[1], size=(4, 3, 16)))
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), FCFG)
    return params, x, y, bidx


def _engine_flcfg(**kw):
    return FLConfig(n_clients=4, clients_per_round=4, lr=0.05, rounds=1,
                    n_clusters=0, loss="mse", **kw)


# --------------------------------------------------- (a) weighted == uniform
@given(st.floats(0.5, 8.0), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=5, deadline=None)
def test_weighted_aggregate_equal_weights_is_uniform(c, seed):
    r = np.random.default_rng(seed)
    stacked = {"a": jnp.asarray(r.normal(size=(5, 3, 2)), jnp.float32),
               "b": [jnp.asarray(r.normal(size=(5, 4)), jnp.float32)]}
    w = jnp.full((5,), c, jnp.float32)
    tree_close(fedavg.weighted_aggregate(stacked, w),
               fedavg.fedavg_aggregate(stacked))


def test_engine_round_equal_counts_matches_uniform_round(fl_data):
    """Sample-count weighting with equal counts == paper's uniform FedAvg."""
    params, x, y, bidx = fl_data
    lr, mu = jnp.float32(0.05), jnp.float32(0.0)
    w = jnp.full((4,), 7.0, jnp.float32)
    p_w, l_w = fedavg.engine_round(params, x, y, bidx, w, lr, mu, FCFG, LOSS)
    p_u, l_u = fedavg.fedavg_round(params, x, y, bidx, lr, FCFG, LOSS)
    tree_close(p_w, p_u)
    np.testing.assert_allclose(float(l_w), float(l_u), rtol=1e-5)


def test_engine_round_unequal_weights_biases_toward_heavy_client(fl_data):
    params, x, y, bidx = fl_data
    lr, mu = jnp.float32(0.05), jnp.float32(0.0)
    heavy = jnp.asarray([1e4, 1.0, 1.0, 1.0], jnp.float32)
    p_h, _ = fedavg.engine_round(params, x, y, bidx, heavy, lr, mu, FCFG, LOSS)
    p_0, _ = local_update(params, x[0], y[0], bidx[0], lr, FCFG, LOSS)
    # nearly all weight on client 0 -> aggregate ~= client 0's local model
    tree_close(p_h, p_0, rtol=1e-3, atol=1e-4)


# --------------------------------------------------- (b) FedProx mu=0
def test_fedprox_mu0_equals_fedavg(fl_data):
    params, x, y, bidx = fl_data
    counts = np.full(4, float(x.shape[1]), np.float32)
    outs = {}
    for opt in ("fedavg_weighted", "fedprox"):
        eng = fedavg.RoundEngine(FCFG, _engine_flcfg(server_opt=opt,
                                                     prox_mu=0.0), loss=LOSS)
        state = server_opt.init_server_state(params, eng.flcfg)
        p, _, l = eng.step(params, state, x, y, bidx, counts)
        outs[opt] = (p, float(l))
    tree_close(outs["fedprox"][0], outs["fedavg_weighted"][0],
               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(outs["fedprox"][1], outs["fedavg_weighted"][1],
                               rtol=1e-6)


def test_fedprox_mu_shrinks_client_drift(fl_data):
    """The proximal term pulls local models toward the round's global model."""
    params, x, y, bidx = fl_data
    lr = jnp.float32(0.1)

    def drift(mu):
        p, _ = local_update(params, x[0], y[0], bidx[0], lr, FCFG, LOSS,
                            prox_mu=jnp.float32(mu))
        sq = jax.tree.map(lambda a, b: float(jnp.sum((a - b) ** 2)), p, params)
        return sum(jax.tree.leaves(sq))

    assert drift(10.0) < drift(0.0)


# ------------------------------------------- (c) adaptive rules, 1 client
@pytest.mark.parametrize("opt", ["fedadam", "fedyogi"])
def test_adaptive_first_step_recovers_averaging_one_client(fl_data, opt):
    """With beta1=0 and server_lr == eps >> |g|, the adaptive step collapses
    to w - g = w_agg: plain averaging of the single client."""
    params, x, y, bidx = fl_data
    flcfg = _engine_flcfg(server_opt=opt, server_beta1=0.0,
                          server_eps=1e6, server_lr=1e6)
    eng = fedavg.RoundEngine(FCFG, flcfg, loss=LOSS)
    state = server_opt.init_server_state(params, flcfg)
    p, _, _ = eng.step(params, state, x[:1], y[:1], bidx[:1],
                       np.ones(1, np.float32))
    p_loc, _ = local_update(params, x[0], y[0], bidx[0], jnp.float32(0.05),
                            FCFG, LOSS)
    tree_close(p, p_loc, rtol=1e-4, atol=1e-5)


def test_server_update_fedavg_lr1_returns_aggregate_exactly():
    w = {"a": jnp.arange(4.0), "b": jnp.ones((2, 3))}
    agg = jax.tree.map(lambda t: t + 0.5, w)
    flcfg = _engine_flcfg(server_opt="fedavg")
    state = server_opt.init_server_state(w, flcfg)
    new, st2 = server_opt.server_update(w, agg, state, flcfg)
    jax.tree.map(np.testing.assert_array_equal, new, agg)
    assert int(st2.t) == 1


def test_server_momentum_accumulates_fedavgm():
    """Constant pseudo-gradient (+1 aggregate offset) + momentum -> the
    server step grows round over round."""
    w = {"a": jnp.zeros(3)}
    flcfg = _engine_flcfg(server_opt="fedavg", server_lr=0.5,
                          server_momentum=0.9)
    state = server_opt.init_server_state(w, flcfg)
    w1, state = server_opt.server_update(
        w, jax.tree.map(lambda t: t + 1.0, w), state, flcfg)
    w2, state = server_opt.server_update(
        w1, jax.tree.map(lambda t: t + 1.0, w1), state, flcfg)
    step1 = float(jnp.abs(w1["a"] - w["a"]).mean())
    step2 = float(jnp.abs(w2["a"] - w1["a"]).mean())
    assert step2 > step1


def test_server_update_rejects_unknown_opt():
    w = {"a": jnp.zeros(2)}
    state = server_opt.init_server_state(w, _engine_flcfg(server_opt="fedavg"))
    with pytest.raises(ValueError):
        server_opt.server_update(w, w, state,
                                 _engine_flcfg(server_opt="fedsgdfoo"))
    with pytest.raises(ValueError):
        fedavg.RoundEngine(FCFG, _engine_flcfg(server_opt="fedsgdfoo"))


# ------------------------------- (d) vmap vs shard_map, every server_opt
@pytest.mark.parametrize("opt", server_opt.SERVER_OPTS)
def test_vmap_and_shard_map_paths_agree(fl_data, opt):
    params, x, y, bidx = fl_data
    lr = {"fedadam": 0.05, "fedyogi": 0.05}.get(opt, 1.0)
    flcfg = _engine_flcfg(server_opt=opt, server_lr=lr, prox_mu=0.01)
    counts = np.full(4, float(x.shape[1]), np.float32)
    e_vmap = fedavg.RoundEngine(FCFG, flcfg, loss=LOSS)
    e_shard = fedavg.RoundEngine(FCFG, flcfg, loss=LOSS, mesh=MESH)
    s0 = server_opt.init_server_state(params, flcfg)
    p1, s1, l1 = e_vmap.step(params, s0, x, y, bidx, counts)
    p2, s2, l2 = e_shard.step(params, s0, x, y, bidx, counts)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    tree_close(p1, p2, rtol=2e-4, atol=1e-6)
    # second round exercises the server-optimizer state on both paths
    p1b, _, _ = e_vmap.step(p1, s1, x, y, bidx, counts)
    p2b, _, _ = e_shard.step(p2, s2, x, y, bidx, counts)
    tree_close(p1b, p2b, rtol=5e-4, atol=1e-6)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs multi-device CPU (run via ./test.sh)")
def test_shard_map_multi_device_matches_vmap(fl_data):
    """2+-device mesh: cross-shard psum aggregation == pseudo-distributed."""
    params, x, y, bidx = fl_data
    mesh = jax.make_mesh((2,), ("clients",))
    flcfg = _engine_flcfg(server_opt="fedavg_weighted")
    counts = np.asarray([3.0, 1.0, 2.0, 2.0], np.float32)
    e_vmap = fedavg.RoundEngine(FCFG, flcfg, loss=LOSS)
    e_shard = fedavg.RoundEngine(FCFG, flcfg, loss=LOSS, mesh=mesh)
    s0 = server_opt.init_server_state(params, flcfg)
    p1, _, l1 = e_vmap.step(params, s0, x, y, bidx, counts)
    p2, _, l2 = e_shard.step(params, s0, x, y, bidx, counts)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    tree_close(p1, p2, rtol=2e-4, atol=1e-6)


# ------------------------------------------------------------- sampling
def test_uniform_sampler_distinct_and_padded():
    rng = np.random.default_rng(0)
    members = np.arange(10, 16)
    sel = sampling.uniform_sampler(rng, members, 4, 0)
    assert len(sel) == 4 and len(set(sel)) == 4
    assert set(sel) <= set(members)
    sel = sampling.uniform_sampler(rng, members, 9, 0)   # m > |members|: pad
    assert len(sel) == 9 and set(sel) <= set(members)


def test_weighted_sampler_prefers_heavy_clients():
    rng = np.random.default_rng(0)
    members = np.arange(8)
    w = np.asarray([50.0] + [1.0] * 7)
    hits = sum(0 in sampling.weighted_sampler(rng, members, 2, t, w)
               for t in range(50))
    assert hits > 40                       # client 0 in nearly every round


def test_round_robin_sampler_visits_all_clients_equally():
    members = np.arange(6) + 100
    rng = np.random.default_rng(0)
    seen = np.concatenate([
        sampling.round_robin_sampler(rng, members, 2, t) for t in range(6)])
    ids, counts = np.unique(seen, return_counts=True)
    assert set(ids) == set(members)
    assert (counts == 2).all()             # 6 rounds x m=2 over 6 members


def test_weighted_sampler_handles_zero_weight_clients():
    """Zero-weight members can't break the exactly-m contract (pad path)."""
    rng = np.random.default_rng(0)
    members = np.arange(5)
    w = np.asarray([1.0, 0.0, 0.0, 0.0, 0.0])
    sel = sampling.weighted_sampler(rng, members, 3, 0, w)
    assert len(sel) == 3 and 0 in sel
    sel = sampling.weighted_sampler(rng, members, 3, 0, np.zeros(5))
    assert len(sel) == 3                   # all-zero -> uniform fallback


def test_weighted_pad_prefers_distinct_unselected_members():
    """Padding contract (ISSUE 4 fix): when the without-replacement weighted
    draw exhausts the nonzero-weight members, the remainder must be DISTINCT
    unselected members — never duplicates of already-selected clients while
    unselected ones remain."""
    members = np.arange(6)
    w = np.asarray([1.0, 1.0, 1.0, 0.0, 0.0, 0.0])
    for seed in range(20):
        sel = sampling.weighted_sampler(np.random.default_rng(seed),
                                        members, 5, 0, w)
        assert len(sel) == 5
        assert len(np.unique(sel)) == 5          # all distinct
        assert {0, 1, 2} <= set(sel)             # every nonzero first


def test_weighted_pad_prefers_nonzero_weight_members():
    """With m > |members| the duplicate passes kick in only after every
    member (nonzero-weight AND zero-weight) was selected once."""
    members = np.arange(4)
    w = np.asarray([2.0, 1.0, 0.0, 0.0])
    for seed in range(10):
        sel = sampling.weighted_sampler(np.random.default_rng(seed),
                                        members, 6, 0, w)
        ids, counts = np.unique(sel, return_counts=True)
        assert set(ids) == set(members)          # everyone in before dups
        assert counts.max() <= 2


def test_uniform_pad_cycles_evenly_instead_of_resampling():
    """m > |members|: duplicates are evenly-cycled shuffles — no member
    appears k+2 times before every member appears k+1 times (the old pad
    resampled WITH replacement and could triple a member while others
    appeared once)."""
    members = np.arange(10, 16)
    for seed in range(20):
        rng = np.random.default_rng(seed)
        sel = sampling.uniform_sampler(rng, members, 9, 0)
        ids, counts = np.unique(sel, return_counts=True)
        assert len(sel) == 9
        assert set(ids) == set(members)          # every member at least once
        assert counts.max() <= 2
    sel = sampling.uniform_sampler(np.random.default_rng(0), members, 12, 0)
    ids, counts = np.unique(sel, return_counts=True)
    assert (counts == 2).all()                   # m = 2n: exactly twice each


def test_make_sampler_rejects_unknown():
    with pytest.raises(ValueError):
        sampling.make_sampler("stratified")


# ------------------------------------------------------- holdout + driver
@given(st.integers(4, 60), st.floats(0.0, 0.5), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_holdout_clients_partition(n, frac, seed):
    rng = np.random.default_rng(seed)
    train, held = partition.holdout_clients(rng, n, frac)
    assert len(train) + len(held) == n
    assert len(held) == int(round(n * frac))
    assert not set(train) & set(held)
    assert set(train) | set(held) == set(range(n))


def test_run_federated_training_with_engine_options(fl_data):
    """Driver end-to-end: holdout + weighted sampling + fedadam server."""
    series = synthetic.generate_buildings("CA", list(range(4)), days=12)
    flcfg = FLConfig(n_clients=4, clients_per_round=2, rounds=2,
                     n_clusters=0, batch_size=16, lr=0.05,
                     server_opt="fedadam", server_lr=0.05,
                     sampling="weighted", holdout_frac=0.25)
    out = fedavg.run_federated_training(series, FCFG, flcfg)
    res = out[-1]
    assert res.loss_history.shape == (2,)
    assert np.isfinite(res.loss_history).all()
    assert res.heldout_clients is not None and len(res.heldout_clients) == 1
    m = fedavg.evaluate_unseen_clients(res.params,
                                       series[res.heldout_clients], FCFG)
    assert 0.0 <= m["accuracy"] <= 100.0
    assert np.isfinite(m["rmse"])


def test_cluster_assignments_full_length_under_holdout():
    """With clustering + holdout, assignments index ALL clients (-1 = held)."""
    series = synthetic.generate_buildings("CA", list(range(6)), days=12)
    flcfg = FLConfig(n_clients=6, clients_per_round=2, rounds=1,
                     n_clusters=2, batch_size=16, cluster_days=6,
                     holdout_frac=0.34)
    out = fedavg.run_federated_training(series, FCFG, flcfg)
    res = next(iter(out.values()))
    assert res.cluster_assignments.shape == (6,)
    held = res.heldout_clients
    assert len(held) == 2
    assert (res.cluster_assignments[held] == -1).all()
    trained = np.setdiff1d(np.arange(6), held)
    assert (res.cluster_assignments[trained] >= 0).all()


def test_run_federated_training_holdout_all_raises():
    series = synthetic.generate_buildings("CA", list(range(4)), days=12)
    flcfg = FLConfig(n_clients=4, clients_per_round=4, rounds=1,
                     n_clusters=0, holdout_frac=1.0)
    with pytest.raises(ValueError):
        fedavg.run_federated_training(series, FCFG, flcfg)
