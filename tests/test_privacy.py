"""Secure aggregation + (eps, delta) accounting (ISSUE 5 tentpole):
pairwise-mask cancellation on every execution path / topology (vmap, flat
psum, hierarchical 2-D mesh, semi-sync cohort-atomic late folds), the
cohort-aware transform-stack plumbing, and the RDP accountant against
independent reference computations."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (FLConfig, ForecasterConfig, PrivacyConfig,
                                SecureAggConfig, TransformConfig)
from repro.core import fedavg, losses, privacy, secure_agg, server_opt, \
    transforms
from repro.data import synthetic, windows

FCFG = ForecasterConfig(cell="lstm", hidden_dim=8)
LOSS = losses.make_loss("mse")


def tree_close(a, b, rtol=1e-4, atol=1e-5):
    jax.tree.map(lambda u, v: np.testing.assert_allclose(
        np.asarray(u), np.asarray(v), rtol=rtol, atol=atol), a, b)


def tree_max_abs_diff(a, b):
    return max(float(jnp.max(jnp.abs(u - v)))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def random_deltas(rng, m, scale=1.0):
    """Client-stacked delta tree (leading axis = clients)."""
    return {"wx": jnp.asarray(rng.normal(size=(m, 4, 3)) * scale,
                              jnp.float32),
            "b": jnp.asarray(rng.normal(size=(m, 5)) * scale, jnp.float32)}


def masked_stack(mask_std=4.0):
    return transforms.make_stack(
        TransformConfig(), SecureAggConfig(enabled=True, mask_std=mask_std))


@pytest.fixture(scope="module")
def fl_data():
    series = synthetic.generate_buildings("CA", list(range(4)), days=12)
    data = windows.batched_client_windows(series, FCFG.lookback, FCFG.horizon)
    x = jnp.asarray(data["x_train"])
    y = jnp.asarray(data["y_train"])
    bidx = jnp.asarray(np.random.default_rng(0)
                       .integers(0, x.shape[1], size=(4, 3, 16)))
    from repro.models import forecaster
    params = forecaster.init_forecaster(jax.random.PRNGKey(0), FCFG)
    return params, x, y, bidx


# ----------------------------------------------------------- config facade
def test_secure_and_privacy_facade_views():
    cfg = FLConfig(secure_agg=True, secure_mask_std=2.5, privacy_delta=1e-6)
    assert cfg.secure == SecureAggConfig(enabled=True, mask_std=2.5)
    assert cfg.privacy == PrivacyConfig(delta=1e-6)
    # secure aggregation forces cohort-atomic semi-sync folds
    assert cfg.async_config.cohort_atomic
    assert not FLConfig().async_config.cohort_atomic
    assert FLConfig(cohort_atomic=True).async_config.cohort_atomic


@pytest.mark.parametrize("kw,needle", [
    (dict(secure_mask_std=0.0), "mask_std"),
    (dict(secure_mask_std=-1.0), "mask_std"),
    (dict(privacy_delta=0.0), "delta"),
    (dict(privacy_delta=1.0), "delta"),
])
def test_facade_validates_secure_privacy_knobs(kw, needle):
    with pytest.raises(ValueError) as ei:
        FLConfig(**kw)
    assert needle in str(ei.value)
    with pytest.raises(ValueError):
        PrivacyConfig(orders=(1,))


def test_make_stack_registers_masker_last_with_stable_tag():
    stack = transforms.make_stack(
        TransformConfig(clip_norm=1.0, noise_multiplier=0.5,
                        quantize_bits=8),
        SecureAggConfig(enabled=True, mask_std=2.0))
    kinds = [type(t).__name__ for t in stack.transforms]
    assert kinds == ["L2Clip", "GaussianNoise", "StochasticQuantize",
                     "PairwiseMasker"]
    assert stack.transforms[-1].tag == 3            # stable PRNG stream id
    assert stack.needs_cohort
    assert not transforms.make_stack(TransformConfig()).needs_cohort
    # disabled secure config adds nothing
    assert not transforms.make_stack(
        TransformConfig(), SecureAggConfig()).transforms


def test_cohort_stack_requires_context():
    stack = masked_stack()
    delta = {"w": jnp.ones((3,))}
    with pytest.raises(ValueError, match="cohort"):
        stack(delta, jax.random.PRNGKey(0))


# ------------------------------------------------------- mask cancellation
def test_pairwise_masks_cancel_in_weighted_sum_with_pads():
    """The core secure-agg property: each upload is the client's WEIGHTED
    contribution under a full-strength mask (never a 1/w_i-scaled one —
    upload secrecy must not depend on the weight), pads (w=0) are excluded
    from the mask cohort, and the UNWEIGHTED sum of masked uploads equals
    the clear weighted sum to float tolerance."""
    rng = np.random.default_rng(0)
    m = 6
    deltas = random_deltas(rng, m)
    w = jnp.asarray([3.0, 1.0, 0.0, 7.0, 2.0, 0.0], jnp.float32)  # 2 pads
    keys = jnp.zeros((m, 2), jnp.uint32)
    masked = fedavg.apply_stack(masked_stack(), deltas, keys, w_full=w,
                                round_key=jax.random.PRNGKey(7))
    real, pads = np.asarray([0, 1, 3, 4]), np.asarray([2, 5])
    wcol = np.asarray(w)
    mask_rows = []
    for k in deltas:
        wk = wcol.reshape((-1,) + (1,) * (deltas[k].ndim - 1))
        mask_part = np.asarray(masked[k]) - wk * np.asarray(deltas[k])
        mask_rows.append(mask_part.reshape(m, -1))
        # pads — cycled DUPLICATES of real clients — upload ZERO: they
        # can't join the mask cohort, and sending their delta in the
        # clear would leak the duplicated client's update
        np.testing.assert_array_equal(np.asarray(masked[k])[pads], 0.0)
    # every real upload carries the same full-strength mask scale,
    # REGARDLESS of its weight (w from 1 to 7): with 3 real partners and
    # mask_std = 4 the per-coordinate mask sigma is 4*sqrt(3) for every
    # client — a 1/w_i- (or w_i-) scaled mask would fall far outside
    sigma = 4.0 * math.sqrt(3.0)
    rms = np.sqrt((np.concatenate(mask_rows, axis=1)[real] ** 2).mean(axis=1))
    assert np.all(rms > 0.6 * sigma) and np.all(rms < 1.6 * sigma)
    # uploads are pre-weighted: their UNWEIGHTED sum is the clear weighted
    # numerator (this is what the aggregator divides by sum(w))
    sums_m = jax.tree.map(lambda d: jnp.sum(d, axis=0), masked)
    sums_c, _ = fedavg._weighted_sums(deltas, w)
    tree_close(sums_m, sums_c, rtol=1e-4, atol=1e-4)


def test_pair_masks_are_antisymmetric_and_replayable():
    """mask_ij = -mask_ji (same shared draw, opposite signs) and masks are
    a pure function of the shared round key."""
    masker = secure_agg.PairwiseMasker(mask_std=3.0)
    zero = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((2,))}
    w = jnp.ones((2,), jnp.float32)
    rk = jax.random.PRNGKey(3)
    key = jax.random.PRNGKey(0)                      # unused by the masker
    m0 = masker(zero, key, secure_agg.CohortContext(jnp.int32(0), w, rk))
    m1 = masker(zero, key, secure_agg.CohortContext(jnp.int32(1), w, rk))
    tree_close(m0, jax.tree.map(lambda x: -x, m1), rtol=1e-6, atol=1e-7)
    assert float(jnp.max(jnp.abs(m0["w"]))) > 1.0    # actually masked
    m0b = masker(zero, key, secure_agg.CohortContext(jnp.int32(0), w, rk))
    jax.tree.map(np.testing.assert_array_equal, m0, m0b)
    m0c = masker(zero, key,
                 secure_agg.CohortContext(jnp.int32(0), w,
                                          jax.random.PRNGKey(4)))
    assert float(jnp.max(jnp.abs(m0["w"] - m0c["w"]))) > 0


def test_masking_composes_with_dp_stack_unchanged_streams():
    """Adding the masker must not shift the clip/noise PRNG streams (stable
    per-kind tags): with unit weights, masked minus clear equals the pure
    mask.  (Quantize is exercised separately by the ring battery — with
    quantize on, masking switches the quantizer to the shared ring grid,
    which is a deliberate change of the quantize output, not a stream
    shift.)"""
    rng = np.random.default_rng(1)
    m = 4
    deltas = random_deltas(rng, m, scale=0.01)
    w = jnp.ones((m,), jnp.float32)
    rk = jax.random.PRNGKey(11)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(rk, jnp.arange(m))
    tcfg = TransformConfig(clip_norm=1.0, noise_multiplier=0.5)
    clear = fedavg.apply_stack(transforms.make_stack(tcfg), deltas, keys)
    masked = fedavg.apply_stack(
        transforms.make_stack(tcfg, SecureAggConfig(enabled=True,
                                                    mask_std=2.0)),
        deltas, keys, w_full=w, round_key=rk)
    pure_mask = fedavg.apply_stack(masked_stack(2.0),
                                   jax.tree.map(jnp.zeros_like, deltas),
                                   keys, w_full=w, round_key=rk)
    tree_close(jax.tree.map(lambda a, b: a - b, masked, clear), pure_mask,
               rtol=1e-5, atol=1e-5)


# ------------------------------------------------ engine-level equivalence
def _engines(fl_kw, mesh=None, mask_std=2.0):
    e_clear = fedavg.RoundEngine(FCFG, FLConfig(**fl_kw), loss=LOSS,
                                 mesh=mesh)
    e_mask = fedavg.RoundEngine(
        FCFG, FLConfig(**fl_kw, secure_agg=True, secure_mask_std=mask_std),
        loss=LOSS, mesh=mesh)
    return e_clear, e_mask


def test_masked_round_equals_clear_vmap(fl_data):
    params, x, y, bidx = fl_data
    kw = dict(n_clients=4, clients_per_round=4, rounds=1, n_clusters=0,
              loss="mse", lr=0.05, dp_clip=1.0,
              server_opt="fedavg_weighted")
    e_clear, e_mask = _engines(kw)
    counts = np.full(4, float(x.shape[1]), np.float32)
    s0 = server_opt.init_server_state(params, e_clear.flcfg)
    p_c, _, l_c = e_clear.step(params, s0, x, y, bidx, counts, round_idx=0)
    p_m, _, l_m = e_mask.step(params, s0, x, y, bidx, counts, round_idx=0)
    np.testing.assert_allclose(float(l_c), float(l_m), rtol=1e-6)
    tree_close(p_c, p_m, rtol=1e-5, atol=1e-5)
    # the masked round is NOT a no-op relabeling: per-client uploads differ
    rk = e_mask.base_round_key(0, 0)
    keys = e_mask.round_keys(0, 4)
    from repro.core.async_engine import client_deltas
    d_m, _ = client_deltas(params, x, y, bidx, keys, jnp.float32(0.05),
                           jnp.float32(0.0), FCFG, LOSS, e_mask.transform,
                           "jnp", e_mask.secure, rk, jnp.asarray(counts))
    d_c, _ = client_deltas(params, x, y, bidx, keys, jnp.float32(0.05),
                           jnp.float32(0.0), FCFG, LOSS, e_clear.transform)
    # the mask on the WIRE quantity w_i * y_i has scale mask_std (the
    # upload itself carries mask_std / w_i — see core/secure_agg.py)
    wdiff = jax.tree.map(
        lambda a, b: (a - b) * counts.reshape((-1,) + (1,) * (a.ndim - 1)),
        d_m, d_c)
    assert max(float(jnp.abs(l).mean()) for l in jax.tree.leaves(wdiff)) > 0.5


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
@pytest.mark.parametrize("agg_kw,mesh_shape,axes", [
    (dict(), (8,), ("clients",)),
    (dict(aggregation="hierarchical", n_regions=2), (2, 4),
     ("region", "clients")),
])
def test_masked_equals_clear_on_mesh_topologies(fl_data, agg_kw, mesh_shape,
                                                axes):
    """Acceptance pin: masked == clear to float tolerance on BOTH the flat
    one-psum and the hierarchical edge->region->cloud reduction, with
    weight-0 mesh-padding duplicates in the cohort."""
    params, x, y, bidx = fl_data
    mesh = jax.make_mesh(mesh_shape, axes)
    kw = dict(n_clients=4, clients_per_round=8, rounds=1, n_clusters=0,
              loss="mse", lr=0.05, dp_clip=1.0,
              server_opt="fedavg_weighted", **agg_kw)
    e_clear, e_mask = _engines(kw, mesh=mesh)
    idx = np.resize(np.arange(4), 8)
    counts = np.full(8, float(x.shape[1]), np.float32)
    counts[4:] = 0.0                                 # mesh pads
    s0 = server_opt.init_server_state(params, e_clear.flcfg)
    args = (params, s0, x[idx], y[idx], bidx[idx], counts)
    p_c, _, l_c = e_clear.step(*args, round_idx=0)
    p_m, _, l_m = e_mask.step(*args, round_idx=0)
    np.testing.assert_allclose(float(l_c), float(l_m), rtol=1e-6)
    tree_close(p_c, p_m, rtol=1e-5, atol=1e-5)


def test_masked_training_replays_bit_identical():
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    flcfg = FLConfig(n_clients=6, clients_per_round=4, rounds=3,
                     n_clusters=0, batch_size=16, lr=0.05, loss="ew_mse",
                     seed=0, dp_clip=1.0, secure_agg=True)
    r1 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    r2 = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    np.testing.assert_array_equal(r1.loss_history, r2.loss_history)
    jax.tree.map(np.testing.assert_array_equal, r1.params, r2.params)


def test_semi_sync_cohort_atomic_late_folds_cancel():
    """Acceptance pin: a semi-sync run with LATE folds — lognormal
    stragglers, buffer_k < m', cohort-atomic pacing — equals the clear run
    with the same pacing to float tolerance: each late cohort folds as one
    group (one shared staleness discount), so its dispatch-round masks
    still cancel."""
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    base = dict(n_clients=6, clients_per_round=4, rounds=6, n_clusters=0,
                batch_size=16, lr=0.05, loss="ew_mse", seed=0,
                mode="semi_sync", over_select=1.5, buffer_k=4,
                staleness_alpha=0.5, stragglers="lognormal",
                straggler_jitter=1.0, dp_clip=1.0)
    r_clear = fedavg.run_federated_training(
        series, FCFG, FLConfig(**base, cohort_atomic=True))[-1]
    r_mask = fedavg.run_federated_training(
        series, FCFG, FLConfig(**base, secure_agg=True,
                               secure_mask_std=2.0))[-1]
    # identical event schedule (masking never changes pacing) ...
    np.testing.assert_array_equal(r_clear.sim_times, r_mask.sim_times)
    # ... identical fold pattern incl. empty flushes (nan loss slots) ...
    np.testing.assert_allclose(r_clear.loss_history, r_mask.loss_history,
                               rtol=1e-5, equal_nan=True)
    fold_rounds = np.flatnonzero(np.isfinite(r_clear.loss_history))
    assert len(fold_rounds) > 0
    tree_close(r_clear.params, r_mask.params, rtol=1e-4, atol=1e-4)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
def test_semi_sync_cohort_atomic_masked_equals_clear_shard_map():
    """Same late-fold pin on the MESH execution path: the sharded client
    stage masks inside the shard_map body (only masked deltas cross shard
    boundaries) and the buffered host-side folds still cancel per cohort."""
    series = synthetic.generate_buildings("CA", list(range(8)), days=20)
    base = dict(n_clients=8, clients_per_round=6, rounds=5, n_clusters=0,
                batch_size=16, lr=0.05, loss="ew_mse", seed=0,
                mode="semi_sync", over_select=1.2, buffer_k=5,
                staleness_alpha=0.5, stragglers="lognormal",
                straggler_jitter=1.0, dp_clip=1.0)
    mesh = jax.make_mesh((8,), ("clients",))
    r_clear = fedavg.run_federated_training(
        series, FCFG, FLConfig(**base, cohort_atomic=True), mesh=mesh)[-1]
    r_mask = fedavg.run_federated_training(
        series, FCFG, FLConfig(**base, secure_agg=True,
                               secure_mask_std=2.0), mesh=mesh)[-1]
    np.testing.assert_allclose(r_clear.loss_history, r_mask.loss_history,
                               rtol=1e-5, equal_nan=True)
    assert np.isfinite(r_clear.loss_history).any()
    tree_close(r_clear.params, r_mask.params, rtol=1e-4, atol=1e-4)


def test_semi_sync_cohort_atomic_folds_whole_cohorts_late():
    """Drive the engine directly: under cohort-atomic pacing every fold is
    a complete dispatch cohort, and with buffer_k < m' stragglers make the
    cohorts fold LATE (tau > 0)."""
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    flcfg = FLConfig(n_clients=6, clients_per_round=4, rounds=6,
                     n_clusters=0, batch_size=16, lr=0.05, loss="ew_mse",
                     seed=0, mode="semi_sync", over_select=1.5, buffer_k=4,
                     staleness_alpha=0.5, stragglers="lognormal",
                     straggler_jitter=1.0, dp_clip=1.0, secure_agg=True)
    engine = fedavg.RoundEngine(FCFG, flcfg)
    prov = windows.ClientWindowProvider.from_series(
        series, FCFG.lookback, FCFG.horizon)
    params, sstate = engine.init(jax.random.PRNGKey(0))
    x, y, counts = prov.round_batch(np.arange(6))
    bidx = np.random.default_rng(0).integers(0, int(counts.min()),
                                             size=(6, 3, 16))
    folded_any = False
    for t in range(6):
        params, sstate, l = engine.step(
            params, sstate, jnp.asarray(x), jnp.asarray(y),
            jnp.asarray(bidx), counts, round_idx=t)
        folded_any = folded_any or np.isfinite(float(l))
        # cohort-atomic invariant: the buffer never holds a PARTIAL folded
        # cohort — every pending dispatch round retains its full size or
        # has been removed entirely
        from collections import Counter
        per_round = Counter(p.dispatch_round
                            for p in engine.async_state.pending)
        for r, cnt in per_round.items():
            assert cnt == engine.async_state.cohort_sizes[r]
    assert folded_any
    assert engine.async_state.late_folds > 0         # cohorts folded late
    assert engine.async_state.max_staleness > 0
    assert engine.async_state.empty_flushes > 0      # and some flushes
    #                                                # completed no cohort


# ------------------------------------------------------------- accountant
def test_rdp_full_participation_closed_form():
    """q = 1 must reduce to the plain Gaussian mechanism: RDP = a/(2 z^2)."""
    for z in (0.8, 1.1, 3.0):
        for a in (2, 7, 32, 64):
            assert privacy.rdp_sampled_gaussian(1.0, z, a) == \
                pytest.approx(a / (2 * z * z))


def test_rdp_matches_direct_binomial_reference():
    """Independent reference: the log-space lgamma/logsumexp implementation
    vs a direct math.comb float summation of the same integer-order
    series."""
    def ref(q, z, a):
        s = sum(math.comb(a, k) * (1 - q) ** (a - k) * q ** k
                * math.exp(k * (k - 1) / (2 * z * z)) for k in range(a + 1))
        return math.log(s) / (a - 1)

    for q, z in [(0.01, 1.0), (0.05, 1.1), (0.2, 2.0), (0.5, 0.9)]:
        for a in (2, 3, 8, 17, 32):
            assert privacy.rdp_sampled_gaussian(q, z, a) == \
                pytest.approx(ref(q, z, a), rel=1e-9)


def test_epsilon_matches_independent_reference_two_settings():
    """Acceptance pin: final epsilon vs a fully independent computation
    (direct binomial sums + direct conversion formula) for two
    (noise, sampling-rate, rounds) settings."""
    def ref_eps(q, z, T, delta, orders):
        def rdp(a):
            s = sum(math.comb(a, k) * (1 - q) ** (a - k) * q ** k
                    * math.exp(k * (k - 1) / (2 * z * z))
                    for k in range(a + 1))
            return math.log(s) / (a - 1)
        return max(0.0, min(
            T * rdp(a) + math.log1p(-1 / a)
            - (math.log(delta) + math.log(a)) / (a - 1) for a in orders))

    orders = tuple(range(2, 33))       # direct float sums stay in range
    for q, z, T in [(0.05, 1.1, 100), (0.2, 2.0, 50)]:
        acct = privacy.PrivacyAccountant(z, q, 1e-5, orders=orders)
        acct.step(T)
        assert acct.epsilon() == pytest.approx(
            ref_eps(q, z, T, 1e-5, orders), rel=1e-9)


def test_epsilon_monotone_in_rounds_and_noise():
    acct = privacy.PrivacyAccountant(1.0, 0.1)
    eps = []
    for _ in range(30):
        acct.step()
        eps.append(acct.epsilon())
    assert all(np.isfinite(eps))
    assert all(b > a for a, b in zip(eps, eps[1:]))  # strictly more spent
    # more noise => less epsilon at equal rounds
    quiet = privacy.PrivacyAccountant(2.0, 0.1)
    quiet.step(30)
    assert quiet.epsilon() < eps[-1]


def test_accountant_disabled_reports_inf_cleanly():
    tc_nonoise = TransformConfig(clip_norm=1.0)
    tc_noclip = TransformConfig(noise_multiplier=0.5)
    pc = PrivacyConfig()
    for tcfg, reason in [(tc_nonoise, "dp_noise"), (tc_noclip, "dp_clip")]:
        acct = privacy.make_accountant(tcfg, pc, 0.1)
        acct.step(100)
        assert not acct.active
        assert acct.epsilon() == math.inf
        rep = acct.report()
        assert not rep["enabled"] and reason in rep["disabled_reason"]
        assert "disabled" in privacy.format_report(rep)
    on = privacy.make_accountant(
        TransformConfig(clip_norm=1.0, noise_multiplier=1.0), pc, 0.1)
    assert on.active and on.epsilon() == 0.0         # nothing spent yet
    assert "eps=" in privacy.format_report(
        dict(on.report(), rounds=1)) or True


def test_training_surfaces_running_epsilon():
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    kw = dict(n_clients=6, clients_per_round=3, rounds=4, n_clusters=0,
              batch_size=16, lr=0.05, loss="ew_mse", seed=0)
    res = fedavg.run_federated_training(
        series, FCFG, FLConfig(**kw, dp_clip=1.0, dp_noise=1.0))[-1]
    assert res.eps_history.shape == (4,)
    assert np.isfinite(res.eps_history).all()
    assert (np.diff(res.eps_history) > 0).all()      # monotone in rounds
    assert res.privacy["enabled"]
    assert res.privacy["epsilon"] == pytest.approx(res.eps_history[-1])
    assert res.privacy["sample_rate"] == pytest.approx(0.5)   # 3 of 6
    assert res.privacy["rounds"] == 4
    # accountant vs an equivalent standalone composition
    ref = privacy.PrivacyAccountant(1.0, 0.5, res.privacy["delta"])
    ref.step(4)
    assert res.privacy["epsilon"] == pytest.approx(ref.epsilon())
    # noise off -> disabled accountant, inf epsilon, no crash
    res_off = fedavg.run_federated_training(series, FCFG,
                                            FLConfig(**kw))[-1]
    assert not res_off.privacy["enabled"]
    assert np.all(np.isinf(res_off.eps_history))


# ------------------------------------- ring masking battery (ISSUE 10)
def tree_equal(a, b):
    """BIT-level equality — the ring pins, not float tolerance."""
    jax.tree.map(lambda u, v: np.testing.assert_array_equal(
        np.asarray(u), np.asarray(v)), a, b)


RING_KW = dict(n_clients=4, clients_per_round=4, rounds=2, n_clusters=0,
               loss="mse", lr=0.05, dp_clip=1.0, quantize_bits=8,
               server_opt="fedavg_weighted")


def _ring_engines(kw, mesh=None):
    """Masked engine vs its CLEAR comparator: same shared-grid ring
    quantizer (``quantize_ring``), no masks."""
    e_clear = fedavg.RoundEngine(
        FCFG, FLConfig(**kw, quantize_ring=True), loss=LOSS, mesh=mesh)
    e_mask = fedavg.RoundEngine(
        FCFG, FLConfig(**kw, secure_agg=True), loss=LOSS, mesh=mesh)
    return e_clear, e_mask


def test_make_stack_rings_quantizer_under_masking():
    """quantize+mask switches the quantizer to the shared ring grid and the
    masker to ring mode; quantize_ring alone is the clear comparator; mask
    without quantize stays float."""
    stack = transforms.make_stack(
        TransformConfig(clip_norm=1.0, quantize_bits=8),
        SecureAggConfig(enabled=True))
    assert stack.ring_spec == (8, 1.0, 0.0)
    assert stack.pre_weighted
    q, masker = stack.transforms[-2], stack.transforms[-1]
    assert isinstance(q, transforms.StochasticQuantize) and q.ring
    assert isinstance(masker, secure_agg.PairwiseMasker)
    assert masker.bits == 8
    # DP noise on -> the ring grid reserves a k-sigma noise-tail margin
    noised = transforms.make_stack(
        TransformConfig(clip_norm=1.0, noise_multiplier=0.5,
                        quantize_bits=8),
        SecureAggConfig(enabled=True))
    assert noised.ring_spec == (
        8, 1.0, transforms.RING_NOISE_TAIL_SIGMAS * 0.5)
    clear = transforms.make_stack(
        TransformConfig(clip_norm=1.0, quantize_bits=8, quantize_ring=True))
    assert clear.ring_spec == (8, 1.0, 0.0)
    assert clear.needs_cohort and clear.pre_weighted
    fstack = transforms.make_stack(TransformConfig(),
                                   SecureAggConfig(enabled=True))
    assert fstack.ring_spec is None and fstack.transforms[-1].bits == 0
    # the flat facade knob reaches the transform view
    assert FLConfig(quantize_bits=8,
                    quantize_ring=True).transform.quantize_ring
    with pytest.raises(ValueError, match="ring"):
        FLConfig(quantize_ring=True)                 # needs quantize_bits


def test_ring_levels_reserve_rounding_headroom():
    assert transforms.ring_levels(8, 4) == 2 ** 7 - 1 - 4
    assert transforms.ring_scale(8, 2.0, 4) == 2.0 / (2 ** 7 - 1 - 4)
    with pytest.raises(ValueError, match="ring"):
        transforms.ring_levels(8, 127)               # cohort too big for b=8
    # noise headroom divides the levels: the freed grid range is the
    # k-sigma noise-tail margin, and the sum bound still fits the ring
    assert transforms.ring_levels(8, 4, noise_headroom=1.0) \
        == (2 ** 7 - 1 - 4) // 2
    lv = transforms.ring_levels(8, 4, noise_headroom=4.0)
    assert lv * (1 + 4.0) + 4 <= 2 ** 7 - 1
    assert transforms.ring_scale(8, 2.0, 4, 1.0) == 2.0 / (
        (2 ** 7 - 1 - 4) // 2)
    with pytest.raises(ValueError, match="ring"):
        transforms.ring_levels(8, 4, noise_headroom=200.0)  # needs wider bits


def test_ring_cap_leaves_noise_tail_untruncated():
    """With DP noise on, the per-client ring cap must not clip the
    Gaussian: the noise-headroom grid keeps saturation down at the k-sigma
    residual, where the headroom-free grid would truncate the noise at
    ~1 sigma and clip roughly a third of the coordinates — biasing the
    sum and voiding the accountant's full-std Gaussian premise."""
    z, m = 1.0, 2
    rng = np.random.default_rng(0)
    # stands for the noised clipped delta the stack hands the quantizer:
    # per-coordinate N(0, (z*C)^2), C = sensitivity = 1
    x = jnp.asarray(rng.normal(0.0, z, size=(20000,)), jnp.float32)
    w = jnp.ones((m,), jnp.float32)
    ctx = secure_agg.CohortContext(jnp.int32(0), w, jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)

    def saturated_frac(headroom):
        q = transforms.StochasticQuantize(8, ring=True, sensitivity=1.0,
                                          noise_headroom=headroom)
        out = np.asarray(q([x], key, ctx)[0])
        levels = transforms.ring_levels(8, m, headroom)
        cap = np.floor(0.5 * levels * (1.0 + headroom)) + 1.0
        assert np.abs(out).max() <= cap       # the sum bound always holds
        return float(np.mean(np.abs(out) >= cap))

    assert saturated_frac(transforms.RING_NOISE_TAIL_SIGMAS * z) < 1e-3
    assert saturated_frac(0.0) > 0.05         # the bug the margin fixes


def test_masked_round_equals_clear_bitwise_vmap(fl_data):
    """THE tentpole pin, vmap path: ring-masked == ring-clear EXACTLY (mask
    cancellation is integer ring arithmetic, not float cancellation)."""
    params, x, y, bidx = fl_data
    e_clear, e_mask = _ring_engines(RING_KW)
    counts = np.asarray([17.0, 5.0, 29.0, 11.0], np.float32)
    s0 = server_opt.init_server_state(params, e_clear.flcfg)
    p_c, _, l_c = e_clear.step(params, s0, x, y, bidx, counts, round_idx=0)
    p_m, _, l_m = e_mask.step(params, s0, x, y, bidx, counts, round_idx=0)
    np.testing.assert_array_equal(np.asarray(l_c), np.asarray(l_m))
    tree_equal(p_c, p_m)
    # and the masked uploads really are ring noise, not the clear ints
    from repro.core.async_engine import client_deltas
    rk = e_mask.base_round_key(0, 0)
    keys = e_mask.round_keys(0, 4)
    d_m, _ = client_deltas(params, x, y, bidx, keys, jnp.float32(0.05),
                           jnp.float32(0.0), FCFG, LOSS, e_mask.transform,
                           "jnp", e_mask.secure, rk, jnp.asarray(counts))
    d_c, _ = client_deltas(params, x, y, bidx, keys, jnp.float32(0.05),
                           jnp.float32(0.0), FCFG, LOSS, e_clear.transform,
                           "jnp", None, rk, jnp.asarray(counts))
    assert tree_max_abs_diff(d_m, d_c) > 8.0         # masked ≠ clear grid
    for leaf in jax.tree.leaves(d_m):                # b-bit ring symbols
        v = np.asarray(leaf)
        np.testing.assert_array_equal(v, np.round(v))
        assert v.min() >= -128 and v.max() < 128


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
@pytest.mark.parametrize("agg_kw,mesh_shape,axes", [
    (dict(), (8,), ("clients",)),
    (dict(aggregation="hierarchical", n_regions=2), (2, 4),
     ("region", "clients")),
])
def test_masked_equals_clear_bitwise_on_mesh(fl_data, agg_kw, mesh_shape,
                                             axes):
    """Ring pin on the flat 8-device and hier 2x4 reductions, with weight-0
    mesh pads in the cohort: still EXACT equality."""
    params, x, y, bidx = fl_data
    mesh = jax.make_mesh(mesh_shape, axes)
    kw = dict(RING_KW, clients_per_round=8, **agg_kw)
    e_clear, e_mask = _ring_engines(kw, mesh=mesh)
    idx = np.resize(np.arange(4), 8)
    counts = np.full(8, float(x.shape[1]), np.float32)
    counts[4:] = 0.0                                 # mesh pads
    s0 = server_opt.init_server_state(params, e_clear.flcfg)
    args = (params, s0, x[idx], y[idx], bidx[idx], counts)
    p_c, _, l_c = e_clear.step(*args, round_idx=0)
    p_m, _, l_m = e_mask.step(*args, round_idx=0)
    np.testing.assert_array_equal(np.asarray(l_c), np.asarray(l_m))
    tree_equal(p_c, p_m)


def test_ring_masked_semi_sync_late_folds_bitwise():
    """Cohort-atomic semi-sync with LATE folds: the host-side per-cohort
    ring decode makes masked == clear exact, empty flushes and all."""
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    base = dict(n_clients=6, clients_per_round=4, rounds=6, n_clusters=0,
                batch_size=16, lr=0.05, loss="ew_mse", seed=0,
                mode="semi_sync", over_select=1.5, buffer_k=4,
                staleness_alpha=0.5, stragglers="lognormal",
                straggler_jitter=1.0, dp_clip=1.0, quantize_bits=8)
    r_clear = fedavg.run_federated_training(
        series, FCFG, FLConfig(**base, quantize_ring=True,
                               cohort_atomic=True))[-1]
    r_mask = fedavg.run_federated_training(
        series, FCFG, FLConfig(**base, secure_agg=True))[-1]
    np.testing.assert_array_equal(r_clear.sim_times, r_mask.sim_times)
    np.testing.assert_array_equal(r_clear.loss_history, r_mask.loss_history)
    assert np.isfinite(r_clear.loss_history).any()
    tree_equal(r_clear.params, r_mask.params)


def test_ring_wraparound_heavy_masks_cancel_exactly():
    """Grid values at the very edge of the int8 ring (±127) under uniform
    masks: individual uploads wrap constantly, yet the ring-reduced sum of
    masked uploads equals the ring-reduced clear sum BIT-exactly."""
    m, bits = 5, 8
    rng = np.random.default_rng(3)
    edge = rng.choice([-127.0, -126.0, 126.0, 127.0], size=(m, 257))
    q = {"w": jnp.asarray(edge, jnp.float32),
         "b": jnp.asarray(rng.integers(-127, 128, (m, 9)), jnp.float32)}
    stack = transforms.TransformStack(
        (secure_agg.PairwiseMasker(bits=bits),))
    w = jnp.ones((m,), jnp.float32)
    rk = jax.random.PRNGKey(9)
    keys = jnp.zeros((m, 2), jnp.uint32)
    v = fedavg.apply_stack(stack, q, keys, w_full=w, round_key=rk)
    # wraparound is actually exercised: masked ≠ clear + const
    assert tree_max_abs_diff(v, q) > 128
    for k in q:
        s_mask = transforms.ring_wrap(jnp.sum(v[k], axis=0), bits)
        s_clear = transforms.ring_wrap(jnp.sum(q[k], axis=0), bits)
        np.testing.assert_array_equal(np.asarray(s_mask),
                                      np.asarray(s_clear))


def test_masked_single_upload_uniform_over_ring():
    """One client's masked upload is uniform over the int8 ring: under a
    fixed seed, every one of the 256 ring values occurs with frequency
    close to n/256 (information-theoretic hiding, not just noise)."""
    n = 1 << 15
    masker = secure_agg.PairwiseMasker(bits=8)
    q = {"w": jnp.full((n,), 37.0, jnp.float32)}     # constant secret
    ctx = secure_agg.CohortContext(jnp.int32(0),
                                   jnp.ones((2,), jnp.float32),
                                   jax.random.PRNGKey(123))
    v = np.asarray(masker(q, jax.random.PRNGKey(0), ctx)["w"])
    assert v.min() >= -128 and v.max() < 128
    counts = np.bincount(v.astype(np.int64) + 128, minlength=256)
    expected = n / 256
    assert counts.min() > 0.5 * expected             # every value occurs,
    assert counts.max() < 2.0 * expected             # none dominates
    # and the constant secret is invisible: the mode is not 37
    spread = counts.std() / expected
    assert spread < 0.2


# ----------------------------------- secure-agg-aware central accounting
def _ref_eps(q, z, T, delta, orders):
    """Fully independent epsilon: direct binomial sums + direct CKS
    conversion (no shared code with core/privacy.py)."""
    def rdp(a):
        s = sum(math.comb(a, k) * (1 - q) ** (a - k) * q ** k
                * math.exp(k * (k - 1) / (2 * z * z))
                for k in range(a + 1))
        return math.log(s) / (a - 1)
    return max(0.0, min(
        T * rdp(a) + math.log1p(-1 / a)
        - (math.log(delta) + math.log(a)) / (a - 1) for a in orders))


def test_secure_agg_accountant_pinned_against_reference():
    """Acceptance pin: the central-DP epsilon equals the independent
    reference at the aggregate multiplier z*sqrt(cohort)."""
    orders = tuple(range(2, 33))
    q, z, cohort, T = 0.25, 0.8, 16, 40
    acct = privacy.secure_agg_accountant(
        TransformConfig(clip_norm=1.0, noise_multiplier=z),
        PrivacyConfig(delta=1e-5, orders=orders), q,
        secure_enabled=True, cohort=cohort)
    acct.step(T)
    assert acct.active and acct.mode == "central:secure-agg"
    assert acct.noise_multiplier == pytest.approx(z * math.sqrt(cohort))
    assert acct.epsilon() == pytest.approx(
        _ref_eps(q, z * math.sqrt(cohort), T, 1e-5, orders), rel=1e-9)


def test_secure_agg_epsilon_tighter_and_monotone():
    tc = TransformConfig(clip_norm=1.0, noise_multiplier=0.7)
    pc = PrivacyConfig()
    per = privacy.make_accountant(tc, pc, 0.2)
    per.step(30)
    cen = privacy.secure_agg_accountant(tc, pc, 0.2, secure_enabled=True,
                                        cohort=8)
    cen.step(30)
    # strictly tighter than the per-client bound at matched noise
    assert cen.epsilon() < per.epsilon()
    assert np.isfinite(cen.epsilon()) and cen.epsilon() > 0
    # monotone in rounds
    run = privacy.secure_agg_accountant(tc, pc, 0.2, secure_enabled=True,
                                        cohort=8)
    eps = []
    for _ in range(10):
        run.step()
        eps.append(run.epsilon())
    assert all(b > a for a, b in zip(eps, eps[1:]))


def test_secure_agg_accountant_disabled_when_masking_off():
    acct = privacy.secure_agg_accountant(
        TransformConfig(clip_norm=1.0, noise_multiplier=1.0),
        PrivacyConfig(), 0.5, secure_enabled=False, cohort=4)
    acct.step(10)
    assert not acct.active
    assert acct.epsilon() == math.inf
    rep = acct.report()
    assert rep["mode"] == "central:secure-agg"
    assert "secure aggregation is off" in rep["disabled_reason"]
    assert "disabled" in privacy.format_report(rep)


def test_secure_agg_accountant_gated_on_ring_and_uniform():
    """Central accounting only prices the RING-masked UNIFORM sum: float
    masking is not information-theoretically hiding, and a weighted sum
    concentrates sensitivity on heavy clients faster than noise."""
    tc = TransformConfig(clip_norm=1.0, noise_multiplier=0.8)
    pc = PrivacyConfig()
    flt = privacy.secure_agg_accountant(tc, pc, 0.25, secure_enabled=True,
                                        cohort=8, ring=False)
    assert not flt.active and flt.epsilon() == math.inf
    assert "float masking" in flt.disabled_reason
    wtd = privacy.secure_agg_accountant(tc, pc, 0.25, secure_enabled=True,
                                        cohort=8, weighted=True)
    assert not wtd.active
    assert "weighted aggregation" in wtd.disabled_reason
    # a FIXED weight vector admits the exact weighted-sum multiplier
    # z * sqrt(sum frac^2) / max frac (uniform -> z*sqrt(m); one dominant
    # client -> z), pinned against the independent reference
    w = np.asarray([4.0, 1.0, 1.0, 1.0, 1.0])
    frac = w / w.sum()
    z_eff = 0.8 * math.sqrt(float(np.sum(frac ** 2))) / float(frac.max())
    orders = tuple(range(2, 33))
    fixed = privacy.secure_agg_accountant(
        tc, PrivacyConfig(orders=orders), 0.25, secure_enabled=True,
        cohort=5, weighted=True, weights=w)
    fixed.step(10)
    assert fixed.active
    assert fixed.noise_multiplier == pytest.approx(z_eff)
    assert fixed.epsilon() == pytest.approx(
        _ref_eps(0.25, z_eff, 10, 1e-5, orders), rel=1e-9)
    # sanity: the weighted multiplier certifies at least the per-client z
    # and at most the uniform z*sqrt(m)
    assert 0.8 <= fixed.noise_multiplier <= 0.8 * math.sqrt(5)
    uni = privacy.secure_agg_accountant(
        tc, pc, 0.25, secure_enabled=True, cohort=4, weighted=True,
        weights=np.asarray([3.0, 3.0, 3.0, 3.0]))
    assert uni.noise_multiplier == pytest.approx(0.8 * math.sqrt(4))


def test_central_accountant_shrinks_to_min_observed_cohort():
    """observe_cohort re-prices the WHOLE run at z*sqrt(min cohort): a
    churn re-key folds a survivor-only sum, so the smaller noise applies
    retroactively (conservative); growing back is ignored, per-client
    accountants are unaffected, and the min survives a state round-trip."""
    tc = TransformConfig(clip_norm=1.0, noise_multiplier=0.8)
    pc = PrivacyConfig(orders=tuple(range(2, 33)))
    acct = privacy.secure_agg_accountant(tc, pc, 0.25, secure_enabled=True,
                                         cohort=8)
    acct.step(5)
    eps_full = acct.epsilon()
    acct.observe_cohort(3)
    assert acct.cohort == 3
    assert acct.noise_multiplier == pytest.approx(0.8 * math.sqrt(3))
    assert acct.epsilon() > eps_full
    ref = privacy.secure_agg_accountant(tc, pc, 0.25, secure_enabled=True,
                                        cohort=3)
    ref.step(5)
    assert acct.epsilon() == pytest.approx(ref.epsilon())
    acct.observe_cohort(6)                    # never grows back
    assert acct.cohort == 3
    # state round-trip carries the min cohort (checkpoint/resume)
    fresh = privacy.secure_agg_accountant(tc, pc, 0.25, secure_enabled=True,
                                          cohort=8)
    fresh.load_state(acct.state_dict())
    assert fresh.cohort == 3
    assert fresh.epsilon() == pytest.approx(acct.epsilon())
    assert acct.report()["cohort"] == 3
    # per-client accountants have no cohort to shrink
    per = privacy.make_accountant(tc, pc, 0.25)
    per.step(5)
    eps_per = per.epsilon()
    per.observe_cohort(1)
    assert per.epsilon() == eps_per and "cohort" not in per.report()


def test_training_surfaces_central_mode_under_ring_masking():
    """FLResult.privacy carries the central mode when RING masking is on
    (quantize + mask, uniform aggregation), with epsilon = the aggregate-
    Gaussian composition (z*sqrt(m') on q=m'/N), strictly tighter than the
    per-client run at matched noise.  Float masking and weighted
    aggregation fall back to per-client accounting with the reason."""
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    kw = dict(n_clients=6, clients_per_round=3, rounds=4, n_clusters=0,
              batch_size=16, lr=0.05, loss="ew_mse", seed=0,
              dp_clip=1.0, dp_noise=1.0)
    res = fedavg.run_federated_training(
        series, FCFG, FLConfig(**kw, secure_agg=True, quantize_bits=8))[-1]
    assert res.privacy["mode"] == "central:secure-agg"
    assert res.privacy["enabled"]
    assert res.privacy["cohort"] == 3            # full cohort, no churn
    ref = privacy.PrivacyAccountant(1.0 * math.sqrt(3), 0.5,
                                    res.privacy["delta"])
    ref.step(4)
    assert res.privacy["epsilon"] == pytest.approx(ref.epsilon())
    res_pc = fedavg.run_federated_training(series, FCFG,
                                           FLConfig(**kw))[-1]
    assert res_pc.privacy["mode"] == "per-client"
    assert res.privacy["epsilon"] < res_pc.privacy["epsilon"]
    # float masking (no quantize): masks are not IT-hiding -> per-client
    res_f = fedavg.run_federated_training(
        series, FCFG, FLConfig(**kw, secure_agg=True))[-1]
    assert res_f.privacy["mode"] == "per-client"
    assert "float masking" in res_f.privacy["central_fallback_reason"]
    assert res_f.privacy["epsilon"] == pytest.approx(
        res_pc.privacy["epsilon"])
    # weighted aggregation under ring masking -> per-client
    res_w = fedavg.run_federated_training(
        series, FCFG, FLConfig(**kw, secure_agg=True, quantize_bits=8,
                               server_opt="fedavg_weighted"))[-1]
    assert res_w.privacy["mode"] == "per-client"
    assert "weighted aggregation" in res_w.privacy["central_fallback_reason"]


def test_semi_sync_accounts_one_invocation_per_dispatch():
    series = synthetic.generate_buildings("CA", list(range(6)), days=20)
    flcfg = FLConfig(n_clients=6, clients_per_round=4, rounds=5,
                     n_clusters=0, batch_size=16, lr=0.05, loss="ew_mse",
                     seed=0, mode="semi_sync", over_select=1.5, buffer_k=4,
                     staleness_alpha=0.5, stragglers="lognormal",
                     straggler_jitter=1.0, dp_clip=1.0, dp_noise=1.0)
    res = fedavg.run_federated_training(series, FCFG, flcfg)[-1]
    assert res.privacy["rounds"] == 5                # one per dispatch
    # over-selection raises the accounted sampling rate: m'=6 of 6 members
    assert res.privacy["sample_rate"] == pytest.approx(1.0)
    assert np.isfinite(res.privacy["epsilon"])
