"""Semi-synchronous buffered rounds (ISSUE 4 tentpole): latency model,
staleness discounting, buffer-flush determinism, and the
semi_sync(buffer_k=m', zero-jitter) == sync bit-equivalence pins on both
execution paths."""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import (AsyncConfig, FLConfig, ForecasterConfig,
                                LatencyConfig)
from repro.core import async_engine, fedavg, latency
from repro.data import synthetic

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)

# same golden workload + constants as tests/test_pipeline_api.py — vmap and
# shard_map pins re-captured for the fold_in engine-init key (the two paths
# differ by one f32 ulp of summation order on these values; see the
# GOLDEN comment in tests/test_pipeline_api.py)
GOLDEN = [0.12595632672309875, 0.055874377489089966, 0.04063640534877777]
GOLDEN_SHARD = [0.12595631182193756, 0.055874377489089966,
                0.04063640907406807]


def _workload(**kw):
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    base = dict(n_clients=6, clients_per_round=4, rounds=3, n_clusters=0,
                batch_size=16, lr=0.05, loss="ew_mse", seed=0)
    base.update(kw)
    return series, FLConfig(**base)


# ------------------------------------------------------------ config facade
def test_async_config_facade_and_validation():
    cfg = FLConfig(mode="semi_sync", over_select=1.5, buffer_k=3,
                   staleness_alpha=0.25, stragglers="lognormal",
                   straggler_jitter=0.7)
    acfg = cfg.async_config
    assert acfg == AsyncConfig(mode="semi_sync", over_select=1.5, buffer_k=3,
                               staleness_alpha=0.25,
                               latency=LatencyConfig(distribution="lognormal",
                                                     jitter=0.7))
    assert FLConfig().async_config.mode == "sync"
    for kw in (dict(mode="async"), dict(over_select=0.5), dict(buffer_k=-1),
               dict(staleness_alpha=-0.1), dict(stragglers="uniform"),
               dict(straggler_jitter=-1.0)):
        with pytest.raises(ValueError):
            FLConfig(**kw)


def test_buffer_frac_validates_and_buffers_stragglers():
    """Relative flush threshold: mutually exclusive with buffer_k, in
    [0, 1], and actually sheds stragglers (resolved against each round's
    dispatch size, so it cannot silently degrade on small memberships)."""
    with pytest.raises(ValueError):
        FLConfig(buffer_k=3, buffer_frac=0.5)
    with pytest.raises(ValueError):
        FLConfig(buffer_frac=1.5)
    kw = dict(mode="semi_sync", over_select=1.5, buffer_frac=0.5,
              stragglers="lognormal", straggler_jitter=1.0, rounds=4)
    series, flcfg = _workload(**kw)
    _, sync_cfg = _workload(stragglers="lognormal", straggler_jitter=1.0,
                            rounds=4)
    r1 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    r2 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    r_sync = fedavg.run_federated_training(series, FCFG, sync_cfg)[-1]
    np.testing.assert_array_equal(r1.loss_history, r2.loss_history)
    assert np.isfinite(r1.loss_history).all()
    assert r1.sim_times[-1] < r_sync.sim_times[-1]


def test_engine_rejects_unreachable_buffer_k():
    _, flcfg = _workload(mode="semi_sync", buffer_k=99)
    with pytest.raises(ValueError) as ei:
        fedavg.RoundEngine(FCFG, flcfg)
    assert "buffer_k" in str(ei.value)


# -------------------------------------------------------- staleness weights
@given(st.floats(0.0, 4.0), st.integers(0, 20), st.integers(1, 20))
@settings(max_examples=20, deadline=None)
def test_staleness_discount_monotone_and_alpha0(alpha, tau, dtau):
    """Larger tau => smaller weight; alpha=0 => no discount; fresh updates
    are never discounted."""
    d1 = float(async_engine.staleness_discount(tau, alpha))
    d2 = float(async_engine.staleness_discount(tau + dtau, alpha))
    assert 0.0 < d1 <= 1.0
    assert d2 <= d1
    if alpha > 0:
        assert d2 < d1
    assert async_engine.staleness_discount(tau, 0.0) == 1.0
    assert async_engine.staleness_discount(0, alpha) == 1.0


# ------------------------------------------------------------ latency model
def test_latency_model_deterministic_and_scales_with_work():
    win = np.asarray([10.0, 20.0, 40.0])
    det = latency.LatencyModel(LatencyConfig(), seed=0,
                               payload=latency.payload_bytes(1000))
    t = det.times(0, win, epochs=2)
    # compute scales linearly with windows x epochs on top of a fixed uplink
    assert t[2] - t[1] == pytest.approx(2 * (t[1] - t[0]))
    np.testing.assert_array_equal(t, det.times(0, win, epochs=2))

    logn = latency.LatencyModel(
        LatencyConfig(distribution="lognormal", jitter=1.0), seed=0,
        payload=latency.payload_bytes(1000))
    a, b = logn.times(3, win, 2), logn.times(3, win, 2)
    np.testing.assert_array_equal(a, b)          # replayable per (seed, round)
    assert np.any(logn.times(4, win, 2) != a)    # but fresh per round


def test_latency_zero_jitter_collapses_to_deterministic():
    win = np.asarray([5.0, 9.0])
    kw = dict(seed=1, payload=4000.0)
    t0 = latency.LatencyModel(LatencyConfig(), **kw).times(0, win, 1)
    for dist in ("lognormal", "heavy_tail"):
        cfg = LatencyConfig(distribution=dist, jitter=0.0)
        np.testing.assert_array_equal(
            latency.LatencyModel(cfg, **kw).times(0, win, 1), t0)


def test_payload_bytes_and_link_budget():
    assert latency.payload_bytes(1000) == 4000.0
    assert latency.payload_bytes(1000, 8) == 1000       # int8 = 4x smaller
    b = latency.link_budget(1000, m_clients=30, n_regions=3,
                            quantize_bits=8)
    assert b["region_fanin_bytes"] == 10 * 1000         # m/R quantized uploads
    assert b["cloud_ingress_bytes"] == 3 * 4000         # R fp32 partials
    assert b["flat_cloud_ingress_bytes"] == 30 * 1000
    flat = latency.link_budget(1000, 30, 1, 8)
    assert flat["cloud_ingress_bytes"] == flat["flat_cloud_ingress_bytes"]
    with pytest.raises(ValueError):
        latency.link_budget(1000, 30, 0)


# ------------------------------------------- sync equivalence + golden pin
def test_sync_mode_golden_loss_pin():
    """mode="sync" (the default) stays bit-identical to the pre-async
    engine on the golden workload."""
    series, flcfg = _workload(mode="sync")
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    np.testing.assert_array_equal(res.loss_history,
                                  np.asarray(GOLDEN, np.float64))
    assert res.sim_times.shape == (3,)
    assert (np.diff(res.sim_times) > 0).all()   # event clock advances


def test_semi_sync_wait_for_all_zero_jitter_equals_sync_vmap():
    """buffer_k = m' (the 0 default) + deterministic latency: every flush is
    a complete fresh dispatch set, so the semi-sync engine must be
    BIT-identical to sync — params and loss history."""
    series, sync_cfg = _workload()
    _, semi_cfg = _workload(mode="semi_sync")
    r_sync = fedavg.run_federated_training(series, FCFG, sync_cfg)[-1]
    r_semi = fedavg.run_federated_training(series, FCFG, semi_cfg)[-1]
    np.testing.assert_array_equal(r_sync.loss_history, r_semi.loss_history)
    jax.tree.map(np.testing.assert_array_equal, r_sync.params, r_semi.params)
    np.testing.assert_array_equal(r_sync.sim_times, r_semi.sim_times)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
def test_semi_sync_wait_for_all_zero_jitter_equals_sync_shard_map():
    series, sync_cfg = _workload()
    _, semi_cfg = _workload(mode="semi_sync")
    mesh = jax.make_mesh((8,), ("clients",))
    r_sync = fedavg.run_federated_training(series, FCFG, sync_cfg,
                                           mesh=mesh)[-1]
    r_semi = fedavg.run_federated_training(series, FCFG, semi_cfg,
                                           mesh=mesh)[-1]
    np.testing.assert_array_equal(r_sync.loss_history, r_semi.loss_history)
    jax.tree.map(np.testing.assert_array_equal, r_sync.params, r_semi.params)
    # and the shard_map semi-sync run equals the shard_map golden pin
    np.testing.assert_array_equal(r_semi.loss_history,
                                  np.asarray(GOLDEN_SHARD, np.float64))


# ------------------------------------------------------- buffered path
STRAG = dict(mode="semi_sync", over_select=1.5, buffer_k=4,
             staleness_alpha=0.5, stragglers="lognormal",
             straggler_jitter=1.0, rounds=4)


def test_buffer_flush_deterministic_under_fixed_seed():
    series, flcfg = _workload(**STRAG)
    r1 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    r2 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    np.testing.assert_array_equal(r1.loss_history, r2.loss_history)
    np.testing.assert_array_equal(r1.sim_times, r2.sim_times)
    jax.tree.map(np.testing.assert_array_equal, r1.params, r2.params)
    assert np.isfinite(r1.loss_history).all()


def test_semi_sync_beats_sync_wall_clock_under_stragglers():
    """The acceptance property: with lognormal stragglers, flushing at
    buffer_k < m' cuts simulated wall-clock vs waiting for the max."""
    series, semi_cfg = _workload(**STRAG)
    _, sync_cfg = _workload(stragglers="lognormal", straggler_jitter=1.0,
                            rounds=4)
    r_semi = fedavg.run_federated_training(series, FCFG, semi_cfg)[-1]
    r_sync = fedavg.run_federated_training(series, FCFG, sync_cfg)[-1]
    assert r_semi.sim_times[-1] < r_sync.sim_times[-1]
    assert np.isfinite(r_semi.loss_history).all()


def test_stragglers_fold_late_with_staleness_discount():
    """Drive the engine directly: a buffer_k < m' flush leaves stragglers
    pending, and they fold into a later round discounted."""
    series, flcfg = _workload(**STRAG)
    engine = fedavg.RoundEngine(FCFG, flcfg)
    assert engine.buffer_k == 4 and engine.dispatch_m(4) == 6
    from repro.data import windows as windows_mod
    prov = windows_mod.ClientWindowProvider.from_series(
        series, FCFG.lookback, FCFG.horizon)
    params, sstate = engine.init(jax.random.PRNGKey(0))
    x, y, counts = prov.round_batch(np.arange(6))
    bidx = np.random.default_rng(0).integers(
        0, int(counts.min()), size=(6, 3, 16))
    import jax.numpy as jnp
    for t in range(3):
        params, sstate, l = engine.step(
            params, sstate, jnp.asarray(x), jnp.asarray(y),
            jnp.asarray(bidx), counts, round_idx=t)
        assert np.isfinite(float(l))
    # 6 dispatched/round, flush at 4 => ~2 stragglers buffered per round
    assert engine.async_state.late_folds > 0 or len(
        engine.async_state.pending) > 0
    assert engine.async_state.max_staleness >= 0
    # reset_pacing clears the event state between trainings
    engine.reset_pacing()
    assert engine.sim_time == 0.0 and not engine.async_state.pending


def test_transform_stack_flows_through_buffered_path():
    """DP clip + noise + quantize on the buffered (slow) path: finite, and
    bit-replayable under the same seed (dispatch-round transform keys)."""
    series, flcfg = _workload(**STRAG, dp_clip=1.0, dp_noise=0.5,
                              quantize_bits=8)
    r1 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    r2 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    assert np.isfinite(r1.loss_history).all()
    jax.tree.map(np.testing.assert_array_equal, r1.params, r2.params)
