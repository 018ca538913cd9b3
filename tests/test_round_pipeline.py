"""The host round loop prepares round t+1 while the device runs round t.

``run_federated_training`` dispatches round t, then selects, draws, builds
and puts round t+1's inputs, and only then waits on round t's loss.  Every
call that mutates host state keeps its serial order, so the run must equal
the plain serial loop, replayed here by hand from the same public pieces,
bit for bit; and a checkpoint written after a prefetch must resume to the
uninterrupted run."""
import jax
import numpy as np
import pytest

from repro.configs.base import FLConfig, ForecasterConfig
from repro.core import fedavg
from repro.data import partition, synthetic, windows

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)
MODES = {
    "sync": {},
    "semi_sync": dict(mode="semi_sync", over_select=1.5, buffer_frac=0.5,
                      stragglers="lognormal", straggler_jitter=1.0),
    "semi_sync_churn": dict(mode="semi_sync", over_select=1.5,
                            buffer_frac=0.5, stragglers="lognormal",
                            straggler_jitter=1.0, absent_prob=0.3),
}


@pytest.fixture(scope="module")
def series():
    return synthetic.generate_buildings("CA", list(range(6)), days=20)


def _flcfg(mode, **kw):
    base = dict(n_clients=6, clients_per_round=4, rounds=4, n_clusters=0,
                batch_size=16, lr=0.05, loss="ew_mse", seed=3)
    base.update(MODES[mode], **kw)
    return FLConfig(**base)


def _serial(series, flcfg):
    """The round loop one stage after another: select, draw, build and
    put round t, step it, wait on its loss; then round t+1."""
    provider = windows.ClientWindowProvider.from_series(
        series, FCFG.lookback, FCFG.horizon, cache_size=len(series))
    _, rng = fedavg._seed_rngs(flcfg.seed)
    engine = fedavg.RoundEngine(FCFG, flcfg)
    ccfg = flcfg.client_opt
    steps = partition.local_steps(provider.n_win_max, ccfg.batch_size,
                                  ccfg.local_epochs)
    members = np.arange(provider.n_clients)
    counts = provider.train_counts.astype(np.float32)
    m_sel = engine.dispatch_m(min(flcfg.clients_per_round, len(members)),
                              len(members))
    engine.attach_accountant(len(members), m_sel)
    params, sstate = engine.init(jax.random.fold_in(
        jax.random.PRNGKey(flcfg.seed), 0))
    engine.reset_pacing()
    losses, sims = [], []
    for t in range(flcfg.rounds):
        avail = members
        if engine.latency.churn.absent_prob > 0.0:
            mask = engine.latency.available(t, members)
            if mask.any():
                avail = members[mask]
        sel = engine.select(rng, avail, min(m_sel, len(avail)), t,
                            counts[avail])
        bidx = partition.ragged_minibatch_indices(rng, counts[sel], steps,
                                                  ccfg.batch_size)
        pad_idx = np.resize(np.arange(len(sel)), m_sel)
        s, w = provider.round_series(sel[pad_idx])
        w[len(sel):] = 0.0
        put = engine.put_clients(s, bidx[pad_idx])
        params, sstate, loss = engine.step(params, sstate, put[0], None,
                                           put[1], w, round_idx=t)
        losses.append(float(loss))
        sims.append(engine.sim_time)
    return np.array(losses), np.array(sims), params


def _assert_same_run(a, b):
    np.testing.assert_array_equal(a.loss_history, b.loss_history)
    np.testing.assert_array_equal(a.sim_times, b.sim_times)
    np.testing.assert_array_equal(a.eps_history, b.eps_history)
    jax.tree.map(np.testing.assert_array_equal, a.params, b.params)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_training_equals_the_serial_loop_replayed_by_hand(series, mode):
    flcfg = _flcfg(mode)
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    losses, sims, params = _serial(series, flcfg)
    assert len(losses) == flcfg.rounds >= 3
    np.testing.assert_array_equal(res.loss_history, losses)
    np.testing.assert_array_equal(res.sim_times, sims)
    jax.tree.map(np.testing.assert_array_equal, res.params, params)


class _Killed(Exception):
    pass


@pytest.mark.parametrize("mode", ["sync", "semi_sync"])
@pytest.mark.parametrize("end", ["stop", "crash"])
def test_resume_after_a_prefetched_round_is_bit_identical(series, tmp_path,
                                                          monkeypatch, mode,
                                                          end):
    """Checkpoints every round.  ``stop``: the first call stops after round
    1, whose successor a call that went on would have prepared ahead.
    ``crash``: round 2's dispatch fails after round 1's checkpoint, which
    was written once round 2's draws were already taken; the checkpoint
    holds the rng as round 1 left it, so the resume replays them."""
    flcfg = _flcfg(mode)
    full = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    ck = tmp_path / "ck"
    if end == "stop":
        fedavg.run_federated_training(series, FCFG, flcfg,
                                      checkpoint_path=ck, checkpoint_every=1,
                                      stop_after_rounds=2)
    else:
        step = fedavg.RoundEngine.step

        def failing(self, *a, round_idx=0, **kw):
            if round_idx == 2:
                raise _Killed
            return step(self, *a, round_idx=round_idx, **kw)

        with monkeypatch.context() as mp:
            mp.setattr(fedavg.RoundEngine, "step", failing)
            with pytest.raises(_Killed):
                fedavg.run_federated_training(series, FCFG, flcfg,
                                              checkpoint_path=ck,
                                              checkpoint_every=1)
    resumed = fedavg.run_federated_training(series, FCFG, flcfg,
                                            checkpoint_path=ck)[-1]
    _assert_same_run(full, resumed)
