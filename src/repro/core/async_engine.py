"""Semi-synchronous buffered rounds (``AsyncConfig``, ``FLConfig.mode``).

Synchronous FedAvg waits for every selected client, so the slowest straggler
gates each round — on the paper's Pi cluster that is the wall-clock
bottleneck.  The semi-sync engine (FedBuff-style, Nguyen et al. 2022; see
PAPERS.md) instead:

1. **over-selects** ``m' = ceil(over_select * m)`` clients per round and
   dispatches them at the current simulated clock (``core/latency.py``
   assigns each a finish time: compute ∝ windows x epochs, uplink ∝
   post-quantize payload, pluggable straggler multiplier);
2. **flushes** the aggregate as soon as the first ``buffer_k`` pending
   updates arrive — the event clock advances to the buffer_k-th finish
   time, never to the straggler's;
3. **folds late arrivals** into whichever later round they land in, with
   staleness-discounted weights ``w_i * (1 + tau_i)^(-alpha)`` (tau =
   rounds late).  A stale delta was computed against the *dispatch-round*
   params, so the buffer stores deltas — already run through the per-client
   transform stack AT DISPATCH with the dispatch-round PRNG key, exactly
   like the sync round body, so the server's straggler buffer never holds
   raw fp32 updates — and the fold is
   ``w <- w + sum(w_tilde_i * delta_i) / sum(w_tilde_i)``, the pipeline's
   own ``_weighted_sums`` weighting fed staleness-discounted weights.

When a flush contains exactly this round's dispatch set and nothing is
buffered — always true for ``buffer_k = m'`` with zero-jitter latency —
the step routes through the engine's fused synchronous round, so that
configuration is **bit-identical** to ``mode="sync"`` on both the vmap and
shard_map execution paths (pinned by test).  The buffer itself lives at the
cloud server, so hierarchical topologies only affect the (unchanged)
client-update stage layout.

**Secure aggregation** (``SecureAggConfig``, ``AsyncConfig.cohort_atomic``):
pairwise masks are applied at dispatch, keyed by the DISPATCH round's shared
key, and cancel only over a complete dispatch cohort — so with masking on,
folds become cohort-ATOMIC: a round's updates wait in the buffer until every
member of that dispatch set has arrived, then fold as one group.  All
members of a late cohort share one staleness tau (current − dispatch round),
hence ONE discount factor, which scales every member's mask equally and
preserves cancellation.  A flush whose clock completes no cohort advances
time without a server step (``SemiSyncState.empty_flushes``).

**Fully-async pacing** (FedAsync-style) is the ``buffer_k=1`` corner: the
clock advances to the EARLIEST in-flight arrival and the server steps per
flush — benchmarked against sync/semi-sync by ``bench_scalability --mode
async``.

**Failure injection** (``ChurnConfig``, PR 6): with ``dropout_prob > 0``
the latency model marks some dispatched uploads as lost mid-flight
(``finish_time = inf`` — replayable per ``(seed, round, slot)``).  The
timeout sweep (:func:`_handle_timeouts`) runs at the top of every step:
plain semi-sync retries the client's retained delta (uplink-only cost, up
to ``max_retries``); cohort-atomic folds instead RE-KEY the whole cohort —
unarrived members are abandoned and the arrived survivors re-mask under the
next key generation restricted to the surviving slots, without the server
ever seeing a pre-mask delta (see ``secure_agg.mask_contribution``).  A
flush whose in-flight set is entirely lost advances nothing
(``empty_flushes``).  With ``dropout_prob == 0`` none of this machinery
runs and the schedule is bit-identical to the churn-free engine.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis import taint as taint_mod
from repro.configs.base import (AggregationConfig, AsyncConfig,
                                ForecasterConfig, SecureAggConfig,
                                TransformConfig)
from repro.core import aggregation as aggregation_mod
from repro.core import secure_agg as secure_agg_mod
from repro.core import server_opt as server_opt_mod
from repro.core import transforms as transforms_mod
from repro.core.client import local_update

PyTree = Any


def staleness_discount(tau, alpha: float):
    """Weight multiplier for an update arriving ``tau`` rounds late:
    ``(1 + tau)^(-alpha)``.  Monotone non-increasing in tau; ``alpha = 0``
    disables the discount; a fresh update (tau = 0) is never discounted."""
    return (1.0 + np.asarray(tau, np.float64)) ** (-float(alpha))


# ------------------------------------------------------------ client stage
@functools.partial(jax.jit,
                   static_argnames=("cfg", "loss", "tcfg", "scfg"))
def client_deltas(params, x, y, batch_idx, keys, lr, prox_mu,
                  cfg: ForecasterConfig, loss: Callable,
                  tcfg: TransformConfig = TransformConfig(),
                  scfg: "SecureAggConfig" = None, round_key=None,
                  w_full=None, slots=None):
    """Local-update + transform stages alone: per-client TRANSFORMED deltas
    ``stack(w_i - w_global)`` + losses, WITHOUT aggregation — the buffered
    server needs each client's contribution individually so it can release
    them on its own clock.  The transform stack runs here, at dispatch, for
    the same reason it runs inside the sync round body: only privatized /
    compressed deltas ever leave the client (the server's straggler buffer
    must not hold raw fp32 updates), and the simulated uplink charges the
    post-quantize payload.  ``keys``: (M, 2) dispatch-round transform keys.

    With secure aggregation, pairwise masks are applied HERE, at dispatch,
    keyed by the dispatch cohort's shared ``round_key`` and gated/scaled by
    the cohort weight vector ``w_full`` — so the buffer holds only masked
    uploads, and a cohort's masks cancel whenever the whole cohort is
    folded together (``AsyncConfig.cohort_atomic``).  ``slots`` carries the
    clients' GLOBAL dispatch slots on the shard_map path (None = local
    view, the vmap case).
    """
    from repro.core import fedavg as fedavg_mod
    locals_, client_loss = jax.vmap(
        local_update, in_axes=(None, 0, 0, 0, None, None, None, None))(
        params, x, y, batch_idx, lr, cfg, loss, prox_mu)
    # taint source (production no-op); the returned deltas ARE the uploads
    # the server's straggler buffer holds, so the exit of this function is
    # the shard boundary flcheck checks on the semi-sync fold path
    locals_ = taint_mod.tag_private(locals_)
    deltas = jax.tree.map(lambda l, g: l - g, locals_, params)
    stack = transforms_mod.make_stack(tcfg, scfg)
    if not stack.is_identity:
        deltas = fedavg_mod.apply_stack(stack, deltas, keys, slots=slots,
                                        w_full=w_full, round_key=round_key)
    return taint_mod.boundary(deltas), client_loss


@functools.lru_cache(maxsize=None)
def make_sharded_client_deltas(mesh, cfg: ForecasterConfig, loss: Callable,
                               tcfg: TransformConfig = TransformConfig(),
                               acfg: AggregationConfig = AggregationConfig(),
                               scfg: "SecureAggConfig" = None):
    """Mesh-sharded client stage: same layout as the fused pipeline round
    (clients over the 1-D axis, or the 2-D (region, clients) grid), but the
    per-client transformed deltas come back stacked instead of reduced —
    the transform stack still runs INSIDE the shard_map body, so only
    privatized/compressed deltas cross shard boundaries.

    With a cohort-aware stack (secure aggregation, or the clear shared-grid
    ring quantizer) the returned fn's signature grows the cohort context,
    mirroring ``fedavg.make_pipeline_round``:
    ``fn(params, x, y, batch_idx, keys, slots, w_full, round_key, lr,
    prox_mu)`` — global ``slots`` shard with the clients, the cohort weight
    vector and round key replicate.
    """
    agg = aggregation_mod.make_aggregator(acfg, mesh)
    pspec = agg.pspec()
    needs_ctx = transforms_mod.make_stack(tcfg, scfg).needs_cohort

    if not needs_ctx:
        def body(params, x, y, batch_idx, keys, lr, prox_mu):
            return client_deltas(params, x, y, batch_idx, keys, lr, prox_mu,
                                 cfg, loss, tcfg)

        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), pspec, pspec, pspec, pspec, P(), P()),
            out_specs=(pspec, pspec),
            check_vma=False))

    def secure_body(params, x, y, batch_idx, keys, slots, w_full, round_key,
                    lr, prox_mu):
        return client_deltas(params, x, y, batch_idx, keys, lr, prox_mu,
                             cfg, loss, tcfg, scfg, round_key,
                             w_full, slots)

    return jax.jit(jax.shard_map(
        secure_body, mesh=mesh,
        in_specs=(P(), pspec, pspec, pspec, pspec, pspec, P(), P(), P(),
                  P()),
        out_specs=(pspec, pspec),
        check_vma=False))


# --------------------------------------------------------- buffered server
@jax.jit
def buffered_aggregate(params, deltas, weights):
    """Fold a flushed buffer of (already-transformed) client deltas into the
    global model: ``w + sum(w_i * delta_i) / sum(w_i)``.

    deltas: client-stacked pytree (leading axis = arrivals, zero-padded);
    weights: (A,) staleness-discounted aggregation weights (0 marks pads,
    which then contribute nothing to either sum).  The weighting math is
    the pipeline's own ``_weighted_sums``.
    """
    from repro.core import fedavg as fedavg_mod
    sums, wsum = fedavg_mod._weighted_sums(deltas, weights)
    return jax.tree.map(lambda g, s: g + s / wsum, params, sums)


@jax.jit
def buffered_aggregate_preweighted(params, deltas, discounts, wsum):
    """Fold PRE-WEIGHTED uploads (float masked path: each delta is already
    ``w_i * delta_i + masks``): numerator weights are the staleness
    discounts ALONE — scaling a masked upload by anything non-uniform
    within its cohort would break mask cancellation, and its ``w_i`` is
    already inside — while the denominator ``wsum`` is the usual sum of
    discounted aggregation weights, supplied by the caller."""
    from repro.core import fedavg as fedavg_mod
    sums, _ = fedavg_mod._weighted_sums(deltas, discounts)
    return jax.tree.map(lambda g, s: g + s / wsum, params, sums)


@dataclasses.dataclass(eq=False)     # identity eq: deltas are array trees
class PendingUpdate:
    """One dispatched-but-not-yet-aggregated client update (host-side).
    ``delta`` is already transformed (clipped/noised/quantized at dispatch
    with the dispatch-round key) — the buffer never holds raw updates.

    ``finish_time = inf`` marks a mid-upload failure (``ChurnConfig``): the
    upload never arrives, and the timeout sweep (``_handle_timeouts``)
    eventually retries or abandons it.  ``retry_round`` is the round the
    update was (re)dispatched — the timeout baseline — and ``slot`` the
    client's dispatch slot, which keys its straggler/dropout draws and its
    position in the secure-agg mask cohort."""
    delta: PyTree                      # np arrays, computed at dispatch
    weight: float                      # base aggregation weight (pre-discount)
    loss: float                        # client's local training loss
    dispatch_round: int
    finish_time: float                 # simulated arrival (absolute seconds)
    slot: int = 0                      # global dispatch slot
    retries: int = 0                   # re-dispatch attempts so far
    retry_round: int = 0               # round of the latest (re)dispatch


def _tree_slice(tree, i: int):
    return jax.tree.map(lambda a: np.asarray(a[i]), tree)


def _ring_wrap_np(x: np.ndarray, bits: int) -> np.ndarray:
    """Host-side twin of ``transforms.ring_wrap``: reduce into the centered
    ring ``[-2^(b-1), 2^(b-1))`` (exact on float-encoded ints < 2^24)."""
    half = float(2 ** (bits - 1))
    return (np.mod(x + half, float(2 ** bits)) - half).astype(x.dtype)


def _stack_padded(pending: List[PendingUpdate], weights: np.ndarray):
    """Stack arrived updates into fixed-capacity (next-pow-2) batches so the
    jitted fold sees a bounded set of shapes (<= log2 traces)."""
    n = len(pending)
    cap = 1 << max(n - 1, 0).bit_length()
    deltas = jax.tree.map(
        lambda *xs: np.stack(xs + (np.zeros_like(xs[0]),) * (cap - n)),
        *[p.delta for p in pending])
    w = np.zeros(cap, np.float32)
    w[:n] = weights
    return deltas, w


class SemiSyncState:
    """The buffered server's host-side event state: pending updates + the
    simulated clock.  One per :class:`~repro.core.fedavg.RoundEngine`;
    reset between independent trainings (per cluster).

    ``cohort_sizes`` tracks how many REAL clients each dispatch round still
    has in the running — the bookkeeping cohort-atomic folds (secure
    aggregation) need to decide when a cohort is complete, decremented when
    a timeout abandons members.  ``cohort_w`` / ``cohort_gen`` carry each
    live cohort's current weight vector and re-key generation (dropout
    recovery re-masks survivors under generation g+1 with the dropped slots
    zeroed).  All three dicts are swept once no pending update references
    their round, so they stay O(live cohorts) on arbitrarily long runs.
    """

    def __init__(self) -> None:
        self.pending: List[PendingUpdate] = []
        self.clock = 0.0
        self.late_folds = 0            # stale updates folded so far
        self.max_staleness = 0         # largest tau seen
        self.cohort_sizes: dict = {}   # dispatch round -> # live dispatched
        self.cohort_w: dict = {}       # dispatch round -> (M,) weight vector
        self.cohort_gen: dict = {}     # dispatch round -> re-key generation
        # dispatch-time sum(base_w): the ring quantizer's shared grid is
        # normalized by it, so the fold's decode needs the ORIGINAL W even
        # after a re-key zeroes dropped slots in cohort_w
        self.cohort_W0: dict = {}      # dispatch round -> float
        self.empty_flushes = 0         # cohort-atomic flushes with no
        #                              # complete cohort (no server step)
        self.rekeys = 0                # cohort re-keys (dropout recovery)
        self.abandoned = 0             # updates dropped for good (timeout)

    def reset(self) -> None:
        self.__init__()

    def _sweep(self) -> None:
        """Drop cohort bookkeeping no pending update references (leak fix:
        entries used to accumulate forever in plain semi-sync mode)."""
        live = {p.dispatch_round for p in self.pending}
        for r in [r for r in self.cohort_sizes if r not in live]:
            self.cohort_sizes.pop(r)
            self.cohort_w.pop(r, None)
            self.cohort_gen.pop(r, None)
            self.cohort_W0.pop(r, None)

    # ---- checkpointing (fedavg.run_federated_training) -------------------
    def to_tree(self):
        """The full event state as a checkpointable pytree of numpy arrays
        (float64 scalars — the simulated clock and finish times round-trip
        exactly, which the bit-identical-resume pin needs)."""
        rounds = sorted(self.cohort_sizes)
        return {
            "clock": np.asarray([self.clock], np.float64),
            "counters": np.asarray(
                [self.late_folds, self.max_staleness, self.empty_flushes,
                 self.rekeys, self.abandoned], np.int64),
            "pending": [
                {"delta": p.delta,
                 "scalars": np.asarray(
                     [p.weight, p.loss, p.dispatch_round, p.finish_time,
                      p.slot, p.retries, p.retry_round], np.float64)}
                for p in self.pending],
            "cohort_rounds": np.asarray(rounds, np.int64),
            "cohort_sizes": np.asarray(
                [self.cohort_sizes[r] for r in rounds], np.int64),
            "cohort_gens": np.asarray(
                [self.cohort_gen.get(r, 0) for r in rounds], np.int64),
            "cohort_W0": np.asarray(
                [self.cohort_W0.get(r, 0.0) for r in rounds], np.float64),
            "cohort_w": (np.stack([np.asarray(self.cohort_w[r], np.float32)
                                   for r in rounds])
                         if rounds else np.zeros((0, 0), np.float32)),
        }

    @classmethod
    def from_tree(cls, tree) -> "SemiSyncState":
        ss = cls()
        ss.clock = float(np.asarray(tree["clock"]).reshape(-1)[0])
        (ss.late_folds, ss.max_staleness, ss.empty_flushes, ss.rekeys,
         ss.abandoned) = (int(v) for v in np.asarray(tree["counters"]))
        for entry in tree["pending"]:
            w, l, dr, ft, slot, rt, rr = (
                float(v) for v in np.asarray(entry["scalars"]))
            ss.pending.append(PendingUpdate(
                delta=jax.tree.map(np.asarray, entry["delta"]),
                weight=w, loss=l, dispatch_round=int(dr), finish_time=ft,
                slot=int(slot), retries=int(rt), retry_round=int(rr)))
        for i, r in enumerate(np.asarray(tree["cohort_rounds"], np.int64)):
            ss.cohort_sizes[int(r)] = int(tree["cohort_sizes"][i])
            ss.cohort_gen[int(r)] = int(tree["cohort_gens"][i])
            ss.cohort_w[int(r)] = np.asarray(tree["cohort_w"][i], np.float32)
            # pre-cohort_W0 checkpoints: the weight vector was never zeroed
            # before the field existed, so its sum is the dispatch-time W
            w0 = tree.get("cohort_W0")
            ss.cohort_W0[int(r)] = (float(w0[i]) if w0 is not None
                                    else float(ss.cohort_w[int(r)].sum()))
        return ss


def _handle_timeouts(engine, round_idx: int, stream: int) -> None:
    """Sweep the pending buffer for abandoned work (``ChurnConfig``): any
    update still unarrived ``timeout_rounds`` dispatches after its latest
    (re)dispatch is presumed lost — the server cannot distinguish a dropped
    upload from a merely slow one, so both are treated alike.

    *Plain semi-sync* (no cohort-atomic folds): the server asks the client to
    re-send its retained transformed delta — uplink-only cost on the re-upload
    latency stream, a fresh dropout draw per attempt, up to
    ``max_retries`` attempts, then the update is abandoned for good.

    *Cohort-atomic folds* (secure aggregation): a lost member means the
    cohort's pairwise masks can never cancel, so the whole cohort re-keys
    (Bonawitz-style recovery): unarrived members are abandoned, the
    surviving (arrived) members re-mask under the next key generation
    restricted to the surviving slots — via the mask-correction algebra of
    :func:`~repro.core.secure_agg.mask_contribution`, so the server never
    holds a pre-mask delta — and re-upload, charged on the re-upload latency
    stream.  Survivors therefore become in-flight again (their re-masked
    upload must arrive before the cohort can fold).  A cohort with no
    survivors is dropped entirely.  Without masking the same scheduling runs
    with no delta rewrite, which is what keeps the masked == clear pins
    valid under churn.
    """
    ss: SemiSyncState = engine.async_state
    churn = engine.latency.churn
    overdue = [p for p in ss.pending
               if p.finish_time > ss.clock
               and round_idx - p.retry_round >= churn.timeout_rounds]
    if not overdue:
        return

    if not engine.async_cfg.cohort_atomic:
        for p in overdue:
            if p.retries >= churn.max_retries:
                ss.pending.remove(p)
                ss.abandoned += 1
                continue
            p.retries += 1
            p.retry_round = round_idx
            re_t = float(engine.latency.reupload_times(
                round_idx, [p.slot], attempt=p.retries)[0])
            drop = bool(engine.latency.dropouts(
                round_idx, [p.slot], attempt=p.retries)[0])
            p.finish_time = float("inf") if drop else ss.clock + re_t
        ss._sweep()
        return

    # cohort-atomic: recover every cohort that lost a member
    ring = transforms_mod.make_stack(engine.transform,
                                     engine.secure).ring_spec
    masker = (secure_agg_mod.make_masker(
                  engine.secure, ring_bits=ring[0] if ring else 0)
              if engine.secure is not None else None)
    for r in sorted({p.dispatch_round for p in overdue}):
        cohort = [p for p in ss.pending if p.dispatch_round == r]
        lost = [p for p in cohort if p.finish_time > ss.clock]
        survivors = [p for p in cohort if p.finish_time <= ss.clock]
        for p in lost:
            ss.pending.remove(p)
        ss.abandoned += len(lost)
        if not survivors:
            # everyone lost: the cohort is gone (sweep drops its books)
            continue
        gen = ss.cohort_gen.get(r, 0)
        w_old = np.asarray(ss.cohort_w[r], np.float32)
        w_new = w_old.copy()
        w_new[[p.slot for p in lost]] = 0.0
        if masker is not None:
            old_key = engine.rekey_key(r, stream, gen)
            new_key = engine.rekey_key(r, stream, gen + 1)
            for p in survivors:
                old_m = jax.device_get(secure_agg_mod.mask_contribution(
                    masker, p.delta, p.slot, w_old, old_key))
                new_m = jax.device_get(secure_agg_mod.mask_contribution(
                    masker, p.delta, p.slot, w_new, new_key))
                if ring:
                    # exact ring algebra: wrap(v - old + new) == the upload
                    # the survivor would have produced under the new key
                    # (congruent mod 2^b; one reduction restores the wire)
                    p.delta = jax.tree.map(
                        lambda d, o, n: _ring_wrap_np(
                            np.asarray(d - o + n), ring[0]),
                        p.delta, old_m, new_m)
                else:
                    p.delta = jax.tree.map(
                        lambda d, o, n: np.asarray(d - o + n),
                        p.delta, old_m, new_m)
        # survivors re-upload their (re-masked) deltas: in-flight again,
        # with a fresh dropout draw — a failed re-upload triggers the next
        # generation's recovery at a later timeout
        slots = np.asarray([p.slot for p in survivors])
        re_t = engine.latency.reupload_times(round_idx, slots,
                                             attempt=gen + 1)
        drop = engine.latency.dropouts(round_idx, slots, attempt=gen + 1)
        for p, t, d in zip(survivors, re_t, drop):
            p.finish_time = float("inf") if d else ss.clock + float(t)
            p.retry_round = round_idx
            p.retries += 1
        ss.cohort_sizes[r] = len(survivors)
        ss.cohort_w[r] = w_new
        ss.cohort_gen[r] = gen + 1
        ss.rekeys += 1
        if engine.accountant is not None:
            # the re-keyed fold will carry only the survivors' noise
            # draws: shrink the central accountant's cohort (it keeps the
            # min over the run and re-prices retroactively — conservative;
            # no-op for per-client accounting)
            engine.accountant.observe_cohort(len(survivors))
    ss._sweep()


def semi_sync_step(engine, params, state, x, y, batch_idx, weights,
                   round_idx: int = 0, stream: int = 0):
    """One semi-synchronous round (``RoundEngine.step`` dispatches here).

    Same contract as the sync step — already-selected (over-selected) client
    data in, ``(params, server_state, loss)`` out — plus the simulated event
    clock advanced on ``engine.async_state``.  The reported loss is the
    discount-weighted mean local loss of the updates actually folded this
    round.
    """
    ss: SemiSyncState = engine.async_state
    acfg: AsyncConfig = engine.async_cfg
    ccfg = engine.flcfg.client_opt
    churn = engine.latency.churn
    if churn.faulty:
        # retry / re-key abandoned work BEFORE this round's dispatch, so a
        # recovered cohort can complete at this very flush
        _handle_timeouts(engine, round_idx, stream)
    w_in = np.asarray(weights, np.float32)
    real = np.flatnonzero(w_in > 0)    # mesh-padding duplicates excluded

    # -- dispatch: assign every real client a simulated finish time; a
    # mid-upload failure (ChurnConfig.dropout_prob) makes it infinite — the
    # upload simply never arrives, and only the timeout sweep notices
    times = engine.latency.times(round_idx, w_in[real], ccfg.local_epochs,
                                 slots=real)
    finish = ss.clock + times
    if churn.faulty:
        finish = np.where(engine.latency.dropouts(round_idx, real),
                          np.inf, finish)

    # -- flush point: clock advances to the k-th earliest arrival among
    # everything in flight (old stragglers + this round's dispatch); a
    # fractional threshold resolves against THIS round's dispatch size, so
    # it adapts to uneven cluster/holdout memberships.  Under cohort-atomic
    # folds the buffer can hold ARRIVED updates whose cohort is still
    # incomplete — those must not gate the clock (they'd pin it to past
    # arrival times forever), so the k-count sees only unarrived work.
    # Dropped uploads (finish = inf) can never gate it either.
    in_flight = [p.finish_time for p in ss.pending
                 if not acfg.cohort_atomic or p.finish_time > ss.clock]
    pend_finish = np.asarray(in_flight + list(finish))
    finite = pend_finish[np.isfinite(pend_finish)]
    if acfg.buffer_frac:
        k_cfg = max(1, int(np.ceil(acfg.buffer_frac * len(finish))))
    else:
        k_cfg = engine.buffer_k
    k = min(k_cfg, len(finite))
    have_flush = len(finite) > 0
    new_clock = (float(np.partition(finite, k - 1)[k - 1]) if have_flush
                 else ss.clock)
    arrive_now = finish <= new_clock

    if not ss.pending and bool(arrive_now.all()):
        # Complete flush of exactly this round's dispatch set, nothing
        # buffered: identical math to a synchronous round (all tau = 0),
        # so route through the fused sync path — this is what makes
        # semi_sync(buffer_k=m', zero jitter) bit-identical to sync.
        ss.clock = new_clock
        return engine._sync_step(params, state, x, y, batch_idx, weights,
                                 round_idx, stream)

    # -- slow path: compute every dispatched client's (transformed) delta
    # now — the simulation reveals them per the event clock — buffer, fold
    lr = jnp.float32(engine.flcfg.lr)
    mu = jnp.float32(engine.prox_mu)
    m = x.shape[0]
    keys = engine.round_keys(round_idx, m, stream)
    base_w = w_in if engine.weighted else (w_in > 0).astype(np.float32)
    if engine._client_fn is not None:
        if engine.needs_ctx:
            rk = engine.base_round_key(round_idx, stream)
            deltas, closs = engine._client_fn(
                params, x, y, batch_idx, keys, jnp.arange(m),
                jnp.asarray(base_w), rk, lr, mu)
        else:
            deltas, closs = engine._client_fn(params, x, y, batch_idx, keys,
                                              lr, mu)
    else:
        rk = (engine.base_round_key(round_idx, stream)
              if engine.needs_ctx else None)
        deltas, closs = client_deltas(params, x, y, batch_idx, keys, lr, mu,
                                      engine.fcfg, engine.loss,
                                      engine.transform, engine.secure, rk,
                                      jnp.asarray(base_w))
    deltas = jax.device_get(deltas)
    closs = np.asarray(closs)
    for j, i in enumerate(real):
        ss.pending.append(PendingUpdate(
            delta=_tree_slice(deltas, int(i)), weight=float(base_w[i]),
            loss=float(closs[i]), dispatch_round=round_idx,
            finish_time=float(finish[j]), slot=int(i),
            retry_round=round_idx))
    ss.cohort_sizes[round_idx] = len(real)
    ss.cohort_w[round_idx] = np.asarray(base_w, np.float32).copy()
    ss.cohort_gen[round_idx] = 0
    ss.cohort_W0[round_idx] = float(np.asarray(base_w, np.float64).sum())

    if not have_flush:
        # EVERYTHING in flight is a dropped upload: nothing can arrive, so
        # buffer the dispatch, leave the clock alone, and wait for the
        # timeout sweep to retry / re-key
        ss.empty_flushes += 1
        return params, state, jnp.asarray(float("nan"))

    arrived = [p for p in ss.pending if p.finish_time <= new_clock]
    if acfg.cohort_atomic:
        # secure aggregation: a cohort's pairwise masks cancel only over
        # the COMPLETE dispatch set, so updates fold only when every member
        # of their dispatch round has arrived — the whole cohort then folds
        # as one group with one shared staleness tau (one shared discount,
        # which scales every member's mask equally).
        got = {}
        for p in arrived:
            got[p.dispatch_round] = got.get(p.dispatch_round, 0) + 1
        complete = {r for r, n in got.items()
                    if n == ss.cohort_sizes.get(r)}
        arrived = [p for p in arrived if p.dispatch_round in complete]
        if not arrived:
            # no complete cohort at this flush clock: advance time, keep
            # everything buffered, skip the server step entirely
            ss.clock = new_clock
            ss.empty_flushes += 1
            return params, state, jnp.asarray(float("nan"))
        # a complete cohort means EVERY live member arrived, so dropping by
        # dispatch round removes exactly the folded updates
        ss.pending = [p for p in ss.pending
                      if p.dispatch_round not in complete]
    else:
        ss.pending = [p for p in ss.pending if p.finish_time > new_clock]
    # the ring decode needs each folded cohort's grid geometry (dispatch
    # size M_r and dispatch-time weight sum W0_r); capture it BEFORE the
    # sweep drops the bookkeeping of fully folded cohorts
    cohort_meta = {r: (int(ss.cohort_w[r].shape[0]),
                       float(ss.cohort_W0[r]))
                   for r in {p.dispatch_round for p in arrived}}
    ss._sweep()
    ss.clock = new_clock

    tau = np.asarray([round_idx - p.dispatch_round for p in arrived])
    ss.late_folds += int((tau > 0).sum())
    ss.max_staleness = max(ss.max_staleness, int(tau.max(initial=0)))
    disc = staleness_discount(tau, acfg.staleness_alpha)
    eff_w = (np.asarray([p.weight for p in arrived]) * disc
             ).astype(np.float32)
    stack = transforms_mod.make_stack(engine.transform, engine.secure)
    ring = stack.ring_spec
    if ring is not None:
        # shared-grid ring uploads: decode per COHORT, host-side — wrap the
        # cohort's summed uploads back into the ring (exact integer mask
        # cancellation), rescale through its grid (scale * W0 recovers
        # sum(w_i * delta_i)), apply the cohort's shared staleness discount,
        # then divide by the usual discounted weight sum
        bits, sensitivity, headroom = ring
        num = jax.tree.map(lambda g: np.zeros_like(np.asarray(g)), params)
        for r in sorted(cohort_meta):
            members = [p for p in arrived if p.dispatch_round == r]
            m_r, w0_r = cohort_meta[r]
            s_r = transforms_mod.ring_scale(bits, sensitivity, m_r, headroom)
            d_r = float(staleness_discount(round_idx - r,
                                           acfg.staleness_alpha))
            coef = np.float32(d_r * s_r * w0_r)
            num = jax.tree.map(
                lambda a, *ds: a + coef * _ring_wrap_np(
                    np.sum(np.stack(ds), axis=0), bits),
                num, *[p.delta for p in members])
        denom = jnp.float32(eff_w.sum())
        w_agg = jax.tree.map(lambda g, s: g + jnp.asarray(s) / denom,
                             params, num)
    elif stack.pre_weighted:
        # float masked uploads already carry w_i: numerator weights are the
        # discounts alone (uniform within a cohort — anything else breaks
        # mask cancellation), denominator the discounted weight sum
        d_stack, disc_stack = _stack_padded(arrived,
                                            disc.astype(np.float32))
        w_agg = buffered_aggregate_preweighted(
            params, jax.tree.map(jnp.asarray, d_stack),
            jnp.asarray(disc_stack), jnp.float32(eff_w.sum()))
    else:
        d_stack, w_stack = _stack_padded(arrived, eff_w)
        w_agg = buffered_aggregate(params,
                                   jax.tree.map(jnp.asarray, d_stack),
                                   jnp.asarray(w_stack))
    losses = np.asarray([p.loss for p in arrived])
    loss = float(np.sum(eff_w * losses) / eff_w.sum())
    params, state = server_opt_mod.server_update(params, w_agg, state,
                                                 engine.flcfg.server)
    return params, state, jnp.asarray(loss)
