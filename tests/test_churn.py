"""Client churn & dropout fault tolerance (ISSUE 6 tentpole): replayable
failure injection in the event clock, timeout-driven re-dispatch, dropout-
robust secure aggregation (cohort re-key), and bit-identical
checkpoint/resume of a killed run."""
import jax
import numpy as np
import pytest

from repro.configs.base import ChurnConfig, FLConfig, ForecasterConfig, \
    LatencyConfig
from repro.core import async_engine, fedavg, latency
from repro.data import synthetic

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)


def _workload(**kw):
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    base = dict(n_clients=6, clients_per_round=4, rounds=3, n_clusters=0,
                batch_size=16, lr=0.05, loss="ew_mse", seed=0)
    base.update(kw)
    return series, FLConfig(**base)


def _spy_engines(monkeypatch):
    """Capture every RoundEngine run_federated_training builds, so tests can
    read the final SemiSyncState counters."""
    engines = []
    real = fedavg.RoundEngine

    class Spy(real):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            engines.append(self)

    monkeypatch.setattr(fedavg, "RoundEngine", Spy)
    return engines


# ------------------------------------------------- failure-injection draws
def test_straggler_draw_follows_slot_value_not_position():
    """The straggler multiplier is seeded by the slot VALUE, so permuting
    the dispatch ordering permutes the finish times with it (it used to be
    positional: slot 0 always got the round's first draw)."""
    lm = latency.LatencyModel(
        LatencyConfig(distribution="lognormal", jitter=1.0), seed=0,
        payload=4000.0)
    win = np.asarray([10.0, 10.0, 10.0])      # equal work isolates the draw
    slots = np.asarray([2, 5, 9])
    t = lm.times(1, win, epochs=1, slots=slots)
    perm = np.asarray([1, 2, 0])
    np.testing.assert_array_equal(
        t[perm], lm.times(1, win[perm], epochs=1, slots=slots[perm]))
    # and distinct slot values get decorrelated draws
    assert len(np.unique(t)) == len(t)


def test_dropout_draws_replayable_and_slot_keyed():
    lm = latency.LatencyModel(LatencyConfig(), seed=3, payload=4000.0,
                              churn=ChurnConfig(dropout_prob=0.5))
    slots = np.arange(32)
    d = lm.dropouts(2, slots)
    np.testing.assert_array_equal(d, lm.dropouts(2, slots))   # replayable
    assert d.any() and not d.all()
    assert np.any(lm.dropouts(3, slots) != d)                 # fresh / round
    assert np.any(lm.dropouts(2, slots, attempt=1) != d)      # fresh / retry
    perm = np.random.default_rng(0).permutation(32)
    np.testing.assert_array_equal(lm.dropouts(2, slots[perm]), d[perm])


def test_absence_draws_replayable_and_off_by_default():
    churn = ChurnConfig(absent_prob=0.4)
    lm = latency.LatencyModel(LatencyConfig(), seed=5, payload=1.0,
                              churn=churn)
    ids = np.arange(20)
    a = lm.available(3, ids)
    np.testing.assert_array_equal(a, lm.available(3, ids))
    assert a.any() and not a.all()
    assert np.any(lm.available(4, ids) != a)
    # the default ChurnConfig injects nothing
    off = latency.LatencyModel(LatencyConfig(), seed=5, payload=1.0)
    assert not off.churn.faulty
    assert off.available(3, ids).all()
    assert not off.dropouts(3, ids).any()


def test_churn_config_facade_and_validation():
    flcfg = FLConfig(n_clients=4, clients_per_round=2, rounds=1,
                     mode="semi_sync", dropout_prob=0.3, absent_prob=0.1,
                     timeout_rounds=3, max_retries=2)
    assert flcfg.churn == ChurnConfig(dropout_prob=0.3, absent_prob=0.1,
                                      timeout_rounds=3, max_retries=2)
    assert flcfg.churn.faulty
    with pytest.raises(ValueError):      # sync rounds block on every upload
        FLConfig(n_clients=4, clients_per_round=2, rounds=1,
                 dropout_prob=0.3)


# --------------------------------------------------- engine under dropout
CHURN = dict(mode="semi_sync", over_select=1.5, staleness_alpha=0.5,
             stragglers="lognormal", straggler_jitter=1.0, rounds=6,
             dropout_prob=0.3, timeout_rounds=1)


def test_churn_off_semi_sync_stays_bit_identical_to_pr5():
    """dropout_prob = 0 must not perturb the event schedule: the buffer_k=m'
    zero-jitter semi-sync run equals the sync run of the same workload,
    bit for bit."""
    series, flcfg = _workload(mode="semi_sync")
    _, sync_cfg = _workload()
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    res_sync = fedavg.run_federated_training(series, FCFG, sync_cfg)[-1]
    np.testing.assert_array_equal(res.loss_history, res_sync.loss_history)
    jax.tree.map(np.testing.assert_array_equal, res.params, res_sync.params)


def test_dropout_run_trains_and_counts_failures(monkeypatch):
    """Injected dropouts surface in the books (abandoned / retried work),
    and the run still reaches a finite loss."""
    engines = _spy_engines(monkeypatch)
    series, flcfg = _workload(**CHURN, buffer_k=4)
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    assert np.isfinite(fedavg.final_loss(res))
    ss = engines[-1].async_state
    assert ss.abandoned > 0 or any(p.retries > 0 for p in ss.pending)
    # replayable: same seed, same schedule, same losses
    res2 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    np.testing.assert_array_equal(res.loss_history, res2.loss_history)
    np.testing.assert_array_equal(res.sim_times, res2.sim_times)


def test_masked_equals_clear_under_dropout_rekey(monkeypatch):
    """The PR 5 masked == clear pin survives churn: timeout scheduling and
    re-keying run identically for any cohort-atomic fold, and the re-masked
    survivor uploads cancel over the surviving set — losses match the
    unmasked run to float tolerance on the SAME event schedule, and the
    recovery path is actually exercised (rekeys > 0)."""
    engines = _spy_engines(monkeypatch)
    series, clear_cfg = _workload(**CHURN, cohort_atomic=True)
    _, masked_cfg = _workload(**CHURN, secure_agg=True)
    r_clear = fedavg.run_federated_training(series, FCFG, clear_cfg)[-1]
    r_masked = fedavg.run_federated_training(series, FCFG, masked_cfg)[-1]
    assert engines[-1].async_state.rekeys > 0
    np.testing.assert_array_equal(r_clear.sim_times, r_masked.sim_times)
    fin = np.isfinite(r_clear.loss_history)
    np.testing.assert_array_equal(fin, np.isfinite(r_masked.loss_history))
    np.testing.assert_allclose(r_clear.loss_history[fin],
                               r_masked.loss_history[fin],
                               rtol=1e-4, atol=1e-6)


def test_ring_masked_equals_clear_under_dropout_rekey(monkeypatch):
    """The ISSUE 10 ring pin under churn: with quantize+mask the re-key
    mask correction runs in the integer ring mod 2^b
    (``delta - old + new`` wrapped), so the masked run is BIT-identical —
    not float-close — to the ring-clear run on the same event schedule,
    with the recovery path exercised (rekeys > 0)."""
    engines = _spy_engines(monkeypatch)
    ring = dict(CHURN, quantize_bits=8, dp_clip=1.0)
    series, clear_cfg = _workload(**ring, quantize_ring=True,
                                  cohort_atomic=True)
    _, masked_cfg = _workload(**ring, secure_agg=True)
    r_clear = fedavg.run_federated_training(series, FCFG, clear_cfg)[-1]
    r_masked = fedavg.run_federated_training(series, FCFG, masked_cfg)[-1]
    assert engines[-1].async_state.rekeys > 0
    np.testing.assert_array_equal(r_clear.sim_times, r_masked.sim_times)
    np.testing.assert_array_equal(r_clear.loss_history,
                                  r_masked.loss_history)
    assert np.isfinite(r_clear.loss_history).any()
    jax.tree.map(np.testing.assert_array_equal, r_clear.params,
                 r_masked.params)


def test_membership_churn_excludes_absent_clients():
    series, flcfg = _workload(mode="semi_sync", absent_prob=0.3, rounds=4,
                              stragglers="lognormal", straggler_jitter=1.0,
                              buffer_k=4)
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    assert np.isfinite(fedavg.final_loss(res))
    res2 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    np.testing.assert_array_equal(res.loss_history, res2.loss_history)


# ------------------------------------------------ SemiSyncState lifecycle
def test_semi_sync_state_reset_clears_everything():
    ss = async_engine.SemiSyncState()
    ss.pending.append(async_engine.PendingUpdate(
        delta={"w": np.zeros(2)}, weight=1.0, loss=0.1, dispatch_round=0,
        finish_time=1.0, slot=2))
    ss.clock = 5.0
    ss.cohort_sizes[0] = 3
    ss.cohort_w[0] = np.ones(3, np.float32)
    ss.cohort_gen[0] = 2
    ss.late_folds, ss.max_staleness = 1, 2
    ss.empty_flushes, ss.rekeys, ss.abandoned = 3, 4, 5
    ss.reset()
    assert not ss.pending and ss.clock == 0.0
    assert not ss.cohort_sizes and not ss.cohort_w and not ss.cohort_gen
    assert (ss.late_folds, ss.max_staleness, ss.empty_flushes, ss.rekeys,
            ss.abandoned) == (0, 0, 0, 0, 0)


def test_cohort_books_swept_in_plain_semi_sync(monkeypatch):
    """Leak fix: cohort bookkeeping used to grow one entry per round forever
    in plain semi-sync.  After any run, the books hold exactly the dispatch
    rounds some pending update still references."""
    engines = _spy_engines(monkeypatch)
    series, flcfg = _workload(mode="semi_sync", over_select=1.5, buffer_k=4,
                              staleness_alpha=0.5, stragglers="lognormal",
                              straggler_jitter=1.0, rounds=12)
    fedavg.run_federated_training(series, FCFG, flcfg)
    ss = engines[-1].async_state
    assert set(ss.cohort_sizes) == {p.dispatch_round for p in ss.pending}
    assert len(ss.cohort_sizes) <= 12


def test_time_to_target_and_final_loss_skip_nan_flushes():
    """Empty cohort-atomic flushes record nan; neither readout may trip on
    them (nan <= target is False; final_loss anchors at the last FINITE)."""
    res = fedavg.FLResult(
        params=None,
        loss_history=np.asarray([np.nan, 0.5, np.nan, 0.2, np.nan]),
        sim_times=np.asarray([1.0, 2.0, 3.0, 4.0, 5.0]))
    assert fedavg.time_to_target(res, 0.5) == 2.0
    assert fedavg.time_to_target(res, 0.3) == 4.0
    assert np.isnan(fedavg.time_to_target(res, 0.1))
    assert fedavg.final_loss(res) == 0.2


# ------------------------------------------------- checkpoint/resume pins
RESUME = dict(mode="semi_sync", over_select=1.5, staleness_alpha=0.5,
              stragglers="lognormal", straggler_jitter=1.0, rounds=6,
              n_clusters=2, secure_agg=True, server_opt="fedadam",
              server_lr=0.05, dp_clip=1.0, dp_noise=0.5,
              dropout_prob=0.15, timeout_rounds=1)


def test_kill_and_resume_bit_identical(tmp_path):
    """The acceptance pin: a run killed mid-training (mid-cluster, with
    in-flight masked uploads, Adam server state, a live accountant and a
    churned event clock) resumes from its checkpoint and lands bit-identical
    to the uninterrupted run — losses, event times, eps history, params."""
    series, flcfg = _workload(**RESUME)
    full = fedavg.run_federated_training(series, FCFG, flcfg)
    ck = tmp_path / "resume_ck"          # no .npz suffix: save/load normalize
    part = fedavg.run_federated_training(series, FCFG, flcfg,
                                         checkpoint_path=ck,
                                         stop_after_rounds=8)
    assert len(part) < len(full) or any(
        len(part[c].loss_history) < flcfg.rounds for c in part)
    resumed = fedavg.run_federated_training(series, FCFG, flcfg,
                                            checkpoint_path=ck)
    assert sorted(resumed) == sorted(full)
    for cid in full:
        np.testing.assert_array_equal(full[cid].loss_history,
                                      resumed[cid].loss_history)
        np.testing.assert_array_equal(full[cid].sim_times,
                                      resumed[cid].sim_times)
        np.testing.assert_array_equal(full[cid].eps_history,
                                      resumed[cid].eps_history)
        jax.tree.map(np.testing.assert_array_equal, full[cid].params,
                     resumed[cid].params)
        assert full[cid].privacy == resumed[cid].privacy


def test_ring_kill_and_resume_bit_identical(tmp_path):
    """Same acceptance pin with the RING wire on (quantize 8 + masking):
    the checkpoint round-trips the per-cohort ring metadata (cohort base
    weights W0) that the host-side ring decode needs, so the resumed run
    still lands bit-identical through late ring folds and re-keys."""
    series, flcfg = _workload(**dict(RESUME, quantize_bits=8))
    full = fedavg.run_federated_training(series, FCFG, flcfg)
    ck = tmp_path / "ring_ck"
    fedavg.run_federated_training(series, FCFG, flcfg, checkpoint_path=ck,
                                  stop_after_rounds=8)
    resumed = fedavg.run_federated_training(series, FCFG, flcfg,
                                            checkpoint_path=ck)
    assert sorted(resumed) == sorted(full)
    for cid in full:
        np.testing.assert_array_equal(full[cid].loss_history,
                                      resumed[cid].loss_history)
        np.testing.assert_array_equal(full[cid].sim_times,
                                      resumed[cid].sim_times)
        jax.tree.map(np.testing.assert_array_equal, full[cid].params,
                     resumed[cid].params)
        assert full[cid].privacy == resumed[cid].privacy


CENTRAL = dict(mode="semi_sync", over_select=1.5, staleness_alpha=0.5,
               stragglers="lognormal", straggler_jitter=1.0, rounds=6,
               n_clusters=2, secure_agg=True, quantize_bits=8,
               dp_clip=1.0, dp_noise=0.5, dropout_prob=0.3,
               timeout_rounds=1)


def test_central_accounting_shrinks_under_rekey_and_resumes(tmp_path):
    """A Bonawitz re-key folds a survivor-only sum, so the central
    accountant (ring masking + uniform aggregation) re-prices the whole
    run at z*sqrt(min survivors): epsilon is never smaller than the
    churn-free run's and strictly larger wherever a re-key shrank the
    cohort.  The shrunk cohort is run history (not derivable from the
    configs), so kill/resume must restore it per cluster — including
    already-finished clusters — for bit-identical privacy reports."""
    series, flcfg = _workload(**CENTRAL)
    full = fedavg.run_federated_training(series, FCFG, flcfg)
    _, clean_cfg = _workload(**dict(CENTRAL, dropout_prob=0.0))
    clean = fedavg.run_federated_training(series, FCFG, clean_cfg)
    for cid in full:
        assert full[cid].privacy["mode"] == "central:secure-agg"
        assert full[cid].privacy["cohort"] <= clean[cid].privacy["cohort"]
        assert (full[cid].privacy["epsilon"]
                >= clean[cid].privacy["epsilon"] - 1e-12)
    assert any(full[cid].privacy["cohort"] < clean[cid].privacy["cohort"]
               for cid in full)                 # a re-key really shrank one
    assert any(full[cid].privacy["epsilon"] > clean[cid].privacy["epsilon"]
               for cid in full)
    ck = tmp_path / "central_ck"
    fedavg.run_federated_training(series, FCFG, flcfg, checkpoint_path=ck,
                                  stop_after_rounds=8)
    resumed = fedavg.run_federated_training(series, FCFG, flcfg,
                                            checkpoint_path=ck)
    assert sorted(resumed) == sorted(full)
    for cid in full:
        np.testing.assert_array_equal(full[cid].eps_history,
                                      resumed[cid].eps_history)
        assert full[cid].privacy == resumed[cid].privacy


def test_resume_rejects_config_mismatch(tmp_path):
    series, flcfg = _workload(mode="semi_sync", rounds=2)
    ck = tmp_path / "ck"
    fedavg.run_federated_training(series, FCFG, flcfg, checkpoint_path=ck,
                                  stop_after_rounds=1)
    _, other = _workload(mode="semi_sync", rounds=2, lr=0.01)
    with pytest.raises(ValueError, match="different"):
        fedavg.run_federated_training(series, FCFG, other,
                                      checkpoint_path=ck)
