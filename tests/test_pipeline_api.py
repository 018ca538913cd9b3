"""Composable federated pipeline API (ISSUE 3 tentpole): typed stage
configs + FLConfig facade, delta-transform stack (clip / DP noise /
quantize), pluggable aggregators (flat + hierarchical edge->region->cloud),
and the bit-identity regression pin for default-config runs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import (AggregationConfig, FLConfig, ForecasterConfig,
                                SamplingConfig, ServerOptConfig,
                                TransformConfig)
from repro.core import aggregation, fedavg, losses, server_opt, transforms
from repro.data import synthetic

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)
LOSS = losses.make_loss("mse")


def tree_close(a, b, rtol=1e-5, atol=1e-6):
    jax.tree.map(lambda u, v: np.testing.assert_allclose(u, v, rtol=rtol,
                                                         atol=atol), a, b)


def random_tree(rng, scale=1.0):
    """A params-shaped pytree with leaves of mixed rank."""
    return {"layers": [{"wx": jnp.asarray(rng.normal(size=(3, 8)) * scale,
                                          jnp.float32),
                        "b": jnp.asarray(rng.normal(size=(8,)) * scale,
                                         jnp.float32)}],
            "head": {"w": jnp.asarray(rng.normal(size=(8, 4)) * scale,
                                      jnp.float32)}}


@pytest.fixture(scope="module")
def fl_data():
    series = synthetic.generate_buildings("CA", list(range(4)), days=12)
    from repro.data import windows
    data = windows.batched_client_windows(series, FCFG.lookback, FCFG.horizon)
    x = jnp.asarray(data["x_train"])
    y = jnp.asarray(data["y_train"])
    bidx = jnp.asarray(np.random.default_rng(0)
                       .integers(0, x.shape[1], size=(4, 3, 16)))
    from repro.models import forecaster
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), FCFG)
    return params, x, y, bidx


# ------------------------------------------------------ config facade
def test_facade_builds_typed_stage_views():
    cfg = FLConfig(lr=0.03, local_epochs=2, batch_size=32, loss="mse",
                   prox_mu=0.1, sampling="weighted", seed=7,
                   server_opt="fedadam", server_lr=0.05, dp_clip=1.5,
                   dp_noise=0.5, quantize_bits=8,
                   aggregation="hierarchical", n_regions=2)
    assert cfg.sampling_config == SamplingConfig(strategy="weighted", seed=7)
    assert cfg.client_opt.lr == 0.03 and cfg.client_opt.batch_size == 32
    assert cfg.client_opt.prox_mu == 0.1 and cfg.client_opt.loss == "mse"
    assert cfg.transform == TransformConfig(clip_norm=1.5,
                                            noise_multiplier=0.5,
                                            quantize_bits=8)
    assert cfg.aggregation_config == AggregationConfig(kind="hierarchical",
                                                       n_regions=2)
    assert cfg.server.name == "fedadam" and cfg.server.lr == 0.05


def test_facade_default_transform_is_identity():
    cfg = FLConfig()
    assert cfg.transform.is_identity
    assert cfg.aggregation_config.kind == "flat"


@pytest.mark.parametrize("kw,needle", [
    (dict(server_opt="fedsgdfoo"), "fedavg"),
    (dict(sampling="stratified"), "uniform"),
    (dict(aggregation="ring"), "flat"),
    (dict(loss="mae"), "ew_mse"),
    (dict(dp_clip=-1.0), "clip_norm"),
    (dict(dp_noise=-0.5), "noise_multiplier"),
    (dict(quantize_bits=1), "quantize_bits"),
    (dict(quantize_bits=16), "quantize_bits"),
    (dict(n_regions=-2), "n_regions"),
])
def test_facade_validates_eagerly_with_choices(kw, needle):
    """Typo'd stage names / bad knobs fail AT CONSTRUCTION, naming the
    valid choices — not rounds-deep inside server_update."""
    with pytest.raises(ValueError) as ei:
        FLConfig(**kw)
    assert needle in str(ei.value)


def test_sub_configs_validate_directly():
    with pytest.raises(ValueError):
        ServerOptConfig(name="sgd")
    with pytest.raises(ValueError):
        SamplingConfig(strategy="all")
    with pytest.raises(ValueError):
        AggregationConfig(kind="tree")


# --------------------------------------------------------- transforms
@given(st.floats(0.1, 5.0), st.integers(0, 2 ** 31 - 1),
       st.floats(0.1, 10.0))
@settings(max_examples=8, deadline=None)
def test_clip_bounds_delta_norm(clip, seed, scale):
    """Post-clip global L2 norm <= C for random pytrees; small deltas pass
    through untouched."""
    rng = np.random.default_rng(seed)
    delta = random_tree(rng, scale)
    clipped = transforms.L2Clip(clip)(delta, jax.random.PRNGKey(0))
    assert float(transforms.global_l2_norm(clipped)) <= clip * (1 + 1e-5)
    if float(transforms.global_l2_norm(delta)) <= clip:
        tree_close(clipped, delta)


@given(st.integers(2, 8), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_quantize_round_trip_error_bound(bits, seed):
    """Dequantized leaves differ from the input by at most one grid step
    ``max|x| / (2^(b-1)-1)`` per coordinate; zero leaves survive exactly."""
    rng = np.random.default_rng(seed)
    delta = random_tree(rng)
    delta["layers"][0]["b"] = jnp.zeros_like(delta["layers"][0]["b"])
    q = transforms.StochasticQuantize(bits)(delta, jax.random.PRNGKey(seed))
    levels = 2 ** (bits - 1) - 1
    for orig, deq in zip(jax.tree.leaves(delta), jax.tree.leaves(q)):
        step = float(jnp.max(jnp.abs(orig))) / levels
        assert float(jnp.max(jnp.abs(deq - orig))) <= step + 1e-6
    np.testing.assert_array_equal(q["layers"][0]["b"], 0.0)


def test_quantize_is_unbiased_in_expectation():
    x = {"w": jnp.full((2000,), 0.3, jnp.float32)}
    q = transforms.StochasticQuantize(8)
    outs = [q(x, jax.random.PRNGKey(i))["w"].mean() for i in range(8)]
    np.testing.assert_allclose(float(jnp.mean(jnp.stack(outs))), 0.3,
                               atol=2e-4)


def test_dp_noise_deterministic_under_fixed_key():
    rng = np.random.default_rng(0)
    delta = random_tree(rng)
    noise = transforms.GaussianNoise(sigma=0.5)
    k = jax.random.PRNGKey(42)
    a, b = noise(delta, k), noise(delta, k)
    jax.tree.map(lambda u, v: np.testing.assert_array_equal(u, v), a, b)
    c = noise(delta, jax.random.PRNGKey(43))
    assert float(jnp.max(jnp.abs(a["head"]["w"] - c["head"]["w"]))) > 0


def test_make_stack_order_and_identity():
    assert transforms.make_stack(TransformConfig()).is_identity
    stack = transforms.make_stack(TransformConfig(
        clip_norm=1.0, noise_multiplier=0.5, quantize_bits=8))
    kinds = [type(t).__name__ for t in stack.transforms]
    assert kinds == ["L2Clip", "GaussianNoise", "StochasticQuantize"]
    # noise sigma honors the clip sensitivity: z * C
    assert stack.transforms[1].sigma == pytest.approx(0.5)


def test_prng_streams_invariant_to_toggling_other_stages():
    """Stage keys fold in a STABLE per-transform tag (ISSUE 4 fix): turning
    clipping on/off must not shift the Gaussian-noise or quantize streams.
    With a delta small enough that the clip is a no-op, stacks with and
    without the clip stage must agree BITWISE."""
    rng = np.random.default_rng(3)
    delta = random_tree(rng, scale=0.01)         # well inside clip_norm
    key = jax.random.PRNGKey(11)
    noop_clip = transforms.L2Clip(1e6)
    for tail in ([transforms.GaussianNoise(0.5)],
                 [transforms.StochasticQuantize(8)],
                 [transforms.GaussianNoise(0.5),
                  transforms.StochasticQuantize(8)]):
        bare = transforms.TransformStack(tuple(tail))(delta, key)
        clipped = transforms.TransformStack((noop_clip, *tail))(delta, key)
        jax.tree.map(np.testing.assert_array_equal, bare, clipped)
    # and via the config path: clip_norm toggled, same facade noise knob
    # (clip sensitivity 1.0 keeps sigma identical across the two stacks)
    s_off = transforms.make_stack(TransformConfig(noise_multiplier=0.5))
    s_on = transforms.make_stack(TransformConfig(clip_norm=1.0,
                                                 noise_multiplier=0.5))
    jax.tree.map(np.testing.assert_array_equal,
                 s_off(delta, key), s_on(delta, key))
    # repeated same-kind stages must still draw INDEPENDENT streams (the
    # per-kind tag is disambiguated by occurrence): two noise stages add
    # two different samples, not the same sample twice
    twice = transforms.TransformStack(
        (transforms.GaussianNoise(0.5), transforms.GaussianNoise(0.5)))
    once = transforms.TransformStack((transforms.GaussianNoise(0.5),))
    doubled = jax.tree.map(lambda d, s: 2 * s - d, delta, once(delta, key))
    got = twice(delta, key)
    assert float(jnp.max(jnp.abs(got["head"]["w"] -
                                 doubled["head"]["w"]))) > 0


def test_engine_dp_noise_replays_under_fixed_seed(fl_data):
    """Same seed + round_idx -> bit-identical noised round; different
    round_idx -> different noise."""
    params, x, y, bidx = fl_data
    flcfg = FLConfig(n_clients=4, clients_per_round=4, lr=0.05, rounds=1,
                     n_clusters=0, loss="mse", dp_clip=1.0, dp_noise=0.5)
    eng = fedavg.RoundEngine(FCFG, flcfg, loss=LOSS)
    counts = np.full(4, float(x.shape[1]), np.float32)
    s0 = server_opt.init_server_state(params, flcfg)
    p1, _, l1 = eng.step(params, s0, x, y, bidx, counts, round_idx=3)
    p2, _, l2 = eng.step(params, s0, x, y, bidx, counts, round_idx=3)
    jax.tree.map(lambda u, v: np.testing.assert_array_equal(u, v), p1, p2)
    p3, _, _ = eng.step(params, s0, x, y, bidx, counts, round_idx=4)
    assert float(jnp.max(jnp.abs(p1["head"]["w"] - p3["head"]["w"]))) > 0
    # concurrent trainings sharing one seed (per-cluster streams) must NOT
    # reuse noise — otherwise differencing two released aggregates would
    # cancel the DP protection
    p4, _, _ = eng.step(params, s0, x, y, bidx, counts, round_idx=3,
                        stream=1)
    assert float(jnp.max(jnp.abs(p1["head"]["w"] - p4["head"]["w"]))) > 0


# --------------------------------------------------------- aggregation
def test_make_aggregator_local_flat_hier():
    assert isinstance(aggregation.make_aggregator(None, None),
                      aggregation.LocalAggregator)
    mesh = jax.make_mesh((1,), ("clients",))
    assert isinstance(aggregation.make_aggregator("flat", mesh),
                      aggregation.FlatAggregator)
    with pytest.raises(ValueError):          # 1-D mesh can't go hierarchical
        aggregation.make_aggregator("hierarchical", mesh)


def test_make_mesh_shapes():
    n_dev = len(jax.devices())
    flat = aggregation.make_mesh()
    assert tuple(flat.axis_names) == ("clients",)
    hier = aggregation.make_mesh(AggregationConfig(kind="hierarchical"))
    assert tuple(hier.axis_names) == ("region", "clients")
    assert hier.shape["region"] * hier.shape["clients"] == n_dev
    if n_dev == 8:                           # test.sh geometry: 2x4 grid
        assert hier.shape["region"] == 2 and hier.shape["clients"] == 4
    with pytest.raises(ValueError):
        aggregation.make_mesh(AggregationConfig(kind="hierarchical",
                                                n_regions=n_dev + 1))


def test_engine_rejects_hierarchical_without_mesh():
    flcfg = FLConfig(n_clients=4, clients_per_round=4, rounds=1,
                     n_clusters=0, loss="mse", aggregation="hierarchical")
    with pytest.raises(ValueError):
        fedavg.RoundEngine(FCFG, flcfg, loss=LOSS)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
@pytest.mark.parametrize("tcfg", [
    TransformConfig(),
    TransformConfig(clip_norm=0.5),          # linear per-client transform
])
def test_hierarchical_matches_flat_on_2x4_mesh(fl_data, tcfg):
    """Edge->region->cloud psum pair over the 2x4 (region, clients) grid ==
    flat one-psum aggregation, for identity and linear transforms."""
    params, x, y, bidx = fl_data
    flat_mesh = jax.make_mesh((8,), ("clients",))
    hier_mesh = jax.make_mesh((2, 4), ("region", "clients"))
    kw = dict(n_clients=4, clients_per_round=8, rounds=1, n_clusters=0,
              loss="mse", lr=0.05, dp_clip=tcfg.clip_norm)
    e_flat = fedavg.RoundEngine(FCFG, FLConfig(**kw), loss=LOSS,
                                mesh=flat_mesh)
    e_hier = fedavg.RoundEngine(
        FCFG, FLConfig(**kw, aggregation="hierarchical", n_regions=2),
        loss=LOSS, mesh=hier_mesh)
    # 8 slots over 4 clients: cycle + mark the duplicates weight-0, exactly
    # like the driver's mesh-divisibility padding
    idx = np.resize(np.arange(4), 8)
    counts = np.full(8, float(x.shape[1]), np.float32)
    counts[4:] = 0.0
    s0 = server_opt.init_server_state(params, e_flat.flcfg)
    args = (params, s0, x[idx], y[idx], bidx[idx], counts)
    p_f, _, l_f = e_flat.step(*args, round_idx=0)
    p_h, _, l_h = e_hier.step(*args, round_idx=0)
    np.testing.assert_allclose(float(l_f), float(l_h), rtol=1e-6)
    tree_close(p_f, p_h, rtol=1e-6, atol=1e-7)


def test_full_pipeline_round_runs_and_is_finite(fl_data):
    """DP clip + noise + int8 quantize + (1-region) hierarchical topology:
    one engine round stays finite and actually changes the params."""
    params, x, y, bidx = fl_data
    n_dev = len(jax.devices())
    r = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    mesh = jax.make_mesh((r, n_dev // r), ("region", "clients"))
    flcfg = FLConfig(n_clients=4, clients_per_round=4, rounds=1,
                     n_clusters=0, loss="mse", lr=0.05, dp_clip=1.0,
                     dp_noise=0.5, quantize_bits=8,
                     aggregation="hierarchical", n_regions=r)
    eng = fedavg.RoundEngine(FCFG, flcfg, loss=LOSS, mesh=mesh)
    m = -(-4 // n_dev) * n_dev
    idx = np.resize(np.arange(4), m)
    counts = np.full(m, float(x.shape[1]), np.float32)
    counts[4:] = 0.0
    s0 = server_opt.init_server_state(params, flcfg)
    p, _, l = eng.step(params, s0, x[idx], y[idx], bidx[idx], counts,
                       round_idx=0)
    assert np.isfinite(float(l))
    assert all(np.isfinite(w).all() for w in jax.tree.leaves(p))
    assert float(jnp.max(jnp.abs(p["head"]["w"] -
                                 params["head"]["w"]))) > 0


# ------------------------------------------------- bit-identity regression
# Golden loss histories for FLConfig defaults on this exact tiny workload,
# re-pinned when the engine-init key derivation moved from
# PRNGKey(seed + cid) to fold_in(PRNGKey(seed), cid) (flcheck FLC003:
# additive seeds collide across (seed, cid) pairs).  Each execution path
# must reproduce its pin bit-for-bit.  The vmap and shard_map pins differ
# in rounds 1 and 3 by one f32 ulp: the vmap path sums the 4 selected
# clients sequentially while the 8-shard psum reduces in tree order, and
# with these init values the two roundings no longer coincide (they
# happened to, bitwise, for the pre-fold_in values — summation ORDER is
# the only difference, pinned per path below).
GOLDEN = [0.12595632672309875, 0.055874377489089966, 0.04063640534877777]
GOLDEN_SHARD = [0.12595631182193756, 0.055874377489089966,
                0.04063640907406807]
GOLDEN_FEDADAM = [0.1233379915356636, 0.08418796956539154,
                  0.052974801510572433]


def _golden_workload():
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    flcfg = FLConfig(n_clients=6, clients_per_round=4, rounds=3,
                     n_clusters=0, batch_size=16, lr=0.05, loss="ew_mse",
                     seed=0)
    return series, flcfg


def test_default_config_loss_history_bit_identical_vmap():
    series, flcfg = _golden_workload()
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    np.testing.assert_array_equal(res.loss_history,
                                  np.asarray(GOLDEN, np.float64))


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
def test_default_config_loss_history_bit_identical_shard_map():
    series, flcfg = _golden_workload()
    mesh = jax.make_mesh((8,), ("clients",))
    res = fedavg.run_federated_training(series, FCFG, flcfg, mesh=mesh)[-1]
    np.testing.assert_array_equal(res.loss_history,
                                  np.asarray(GOLDEN_SHARD, np.float64))


def test_engine_options_loss_history_bit_identical():
    """fedadam + weighted sampling + holdout, legacy flat construction."""
    series, _ = _golden_workload()
    flcfg = FLConfig(n_clients=6, clients_per_round=4, rounds=3,
                     n_clusters=0, batch_size=16, lr=0.05, loss="ew_mse",
                     seed=0, server_opt="fedadam", server_lr=0.05,
                     sampling="weighted", holdout_frac=0.2)
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    np.testing.assert_array_equal(res.loss_history,
                                  np.asarray(GOLDEN_FEDADAM, np.float64))


def test_pipeline_round_identity_equals_legacy_engine_round(fl_data):
    """The pipeline round with the identity stack IS the legacy round,
    bitwise — vmap and (1-device) shard_map paths."""
    params, x, y, bidx = fl_data
    w = jnp.full((4,), 7.0, jnp.float32)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(jax.random.PRNGKey(0),
                                                   jnp.arange(4))
    lr, mu = jnp.float32(0.05), jnp.float32(0.0)
    p_new, l_new = fedavg.pipeline_round(params, x, y, bidx, w, keys, lr,
                                         mu, FCFG, LOSS, TransformConfig())
    p_old, l_old = fedavg.engine_round(params, x, y, bidx, w, lr, mu,
                                       FCFG, LOSS)
    jax.tree.map(np.testing.assert_array_equal, p_new, p_old)
    np.testing.assert_array_equal(np.asarray(l_new), np.asarray(l_old))

    mesh = jax.make_mesh((1,), ("clients",))
    new_fn = fedavg.make_pipeline_round(mesh, FCFG, LOSS)
    old_fn = fedavg.make_sharded_engine_round(mesh, FCFG, LOSS)
    p_new, l_new = new_fn(params, x, y, bidx, w, keys, lr, mu)
    p_old, l_old = old_fn(params, x, y, bidx, w, lr, mu)
    jax.tree.map(np.testing.assert_array_equal, p_new, p_old)
    np.testing.assert_array_equal(np.asarray(l_new), np.asarray(l_old))


def test_run_federated_training_auto_builds_hierarchical_mesh():
    """aggregation="hierarchical" with mesh=None builds the (region,
    clients) grid itself and trains end-to-end."""
    series = synthetic.generate_buildings("CA", list(range(4)), days=12)
    flcfg = FLConfig(n_clients=4, clients_per_round=4, rounds=2,
                     n_clusters=0, batch_size=16, lr=0.05, loss="mse",
                     dp_clip=1.0, quantize_bits=8,
                     aggregation="hierarchical")
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    assert res.loss_history.shape == (2,)
    assert np.isfinite(res.loss_history).all()
