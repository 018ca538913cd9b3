"""Federated round engine (paper Alg. 1, generalized) — one explicit pipeline
of typed stages, executed pseudo-distributed (vmap) or mesh-sharded
(shard_map):

    select -> local-update -> transform(deltas) -> aggregate -> server-update

*select* picks the round's participants (``core/sampling.py``,
``SamplingConfig``); each selected client runs ``ClientUpdate`` — E local
epochs of minibatch SGD, optionally FedProx-regularized (``core/client.py``,
``ClientOptConfig``); each client's update delta ``w_i - w_global`` passes
through the *transform* stack — per-client L2 clip -> Gaussian DP noise ->
stochastic int quantize (``core/transforms.py``, ``TransformConfig``) —
INSIDE the round body, before any collective, so on the mesh path only
privatized/compressed deltas ever cross shard boundaries; *aggregate* reduces
the sample-count-weighted deltas through a pluggable topology
(``core/aggregation.py``, ``AggregationConfig``: flat one-psum, or
hierarchical edge->region->cloud over a 2-D (region, clients) mesh); finally
the server applies a *server optimizer* to the pseudo-gradient
``w_global - w_agg`` (``core/server_opt.py``, ``ServerOptConfig``) outside
the round body, shared bit-for-bit by both execution paths.

A round is built in one place, :func:`_pipeline_body`: :func:`pipeline_round`
runs it under vmap, :func:`make_pipeline_round` inside a shard_map body.
Uniform FedAvg (``w <- (1/|s|) Σ w_i``) is the default configuration of that
pipeline, not a special code path.  With the identity transform stack the
round aggregates the raw local models as the weighted mean
``Σ w_i local_i / Σ w_i``, and ``tests/test_pipeline_api.py`` pins it: a
default-config run's loss history matches the plain reference
``bench/references/forecaster.py::fedavg_rounds``, and the vmap path equals
the mesh path bit for bit.  Local epochs run with NO cross-client
communication, which is precisely what makes FedAvg cheaper on the wire
than synchronous data-parallel SGD.

Engine selection is driven entirely by the ``FLConfig`` facade::

    FLConfig(server_opt="fedadam", server_lr=0.05, sampling="weighted",
             dp_clip=1.0, dp_noise=0.5, quantize_bits=8,
             aggregation="hierarchical", n_regions=2, ...)

whose typed stage views (``.sampling_config``, ``.client_opt``,
``.transform``, ``.aggregation_config``, ``.server``) are validated eagerly
at construction.

Round PACING is orthogonal to the stage pipeline: ``FLConfig.mode`` selects
synchronous rounds (default — the slowest selected client gates the round on
the simulated event clock, ``core/latency.py``) or semi-synchronous buffered
rounds (``core/async_engine.py`` — over-select, flush at the ``buffer_k``-th
arrival, fold stragglers later with staleness-discounted weights).
``RoundEngine.step`` dispatches on the mode; ``FLResult.sim_times`` reports
the simulated wall clock either way.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import checkpoint as checkpoint_mod
from repro.analysis import taint as taint_mod
from repro.configs.base import (AggregationConfig, FLConfig, ForecasterConfig,
                                ModelSpec, SecureAggConfig, TransformConfig)
from repro.core import aggregation as aggregation_mod
from repro.core import clustering, losses as losses_mod
from repro.core import privacy as privacy_mod
from repro.core import sampling as sampling_mod
from repro.core import secure_agg as secure_agg_mod
from repro.core import server_opt as server_opt_mod
from repro.core import transforms as transforms_mod
from repro.core.client import local_update
from repro.data import partition, windows
from repro.models import forecaster


# ------------------------------------------------------------- aggregation
def _weighted_sums(stacked_params, weights):
    """Per-shard weighted sums: the ONE place the weighting math lives.

    Returns (tree of Σ_i weight_i * w_i, Σ_i weight_i).  Both execution
    paths build their average from this — the vmap path divides directly,
    the shard_map path psums numerator and denominator first — so any
    future change to the weighting (clipping, DP noise, ...) applies to
    both automatically.
    """
    def ws(w):
        wt = weights.reshape((-1,) + (1,) * (w.ndim - 1))
        return jnp.sum(w * wt, axis=0)

    return jax.tree.map(ws, stacked_params), jnp.sum(weights)


# ------------------------------------------------------- pipeline execution
def apply_stack(stack, deltas, keys, *, slots=None, w_full=None,
                round_key=None):
    """Transform a client-stacked delta tree through ``stack`` (vmapped).

    Cohort-aware stacks (secure aggregation) additionally thread each
    client its :class:`~repro.core.secure_agg.CohortContext`: its GLOBAL
    dispatch slot, the cohort's full weight vector, and the shared round
    key.  On the vmap path ``slots``/``w_full`` default to the local view
    (which IS the cohort); shard_map callers must pass the global ones.
    """
    if not stack.needs_cohort:
        return jax.vmap(stack)(deltas, keys)
    if round_key is None:
        raise ValueError("cohort-aware transform stack needs the shared "
                         "round_key (engine.base_round_key)")
    if w_full is None:
        raise ValueError("cohort-aware transform stack needs the cohort "
                         "weight vector w_full")
    if slots is None:
        slots = jnp.arange(w_full.shape[0])

    def one(delta, key, slot):
        ctx = secure_agg_mod.CohortContext(slot, w_full, round_key)
        return stack(delta, key, ctx)

    return jax.vmap(one)(deltas, keys, slots)


def _pipeline_body(params, x, y, batch_idx, weights, keys, lr, prox_mu, *,
                   cfg: ModelSpec, loss: Callable, tcfg: TransformConfig,
                   agg: "aggregation_mod.Aggregator",
                   scfg: Optional[SecureAggConfig] = None, round_key=None,
                   slots=None, w_full=None):
    """Shared local-update -> transform -> aggregate stages of one round.

    Runs inside vmap (``agg = LocalAggregator``) or inside the shard_map body
    (``agg`` = flat / hierarchical), so both execution paths and every
    topology share ONE implementation of the stage math.  With the identity
    transform stack the raw local models are aggregated as their weighted
    mean (:func:`_weighted_sums`); with transforms the per-client deltas
    are transformed BEFORE the collective and the aggregate is rebuilt as
    ``w_global + avg(transformed deltas)``.  With
    secure aggregation the stack is cohort-aware: the extra
    ``round_key`` / ``slots`` / ``w_full`` args feed the pairwise masker,
    whose masks cancel in ``agg.reduce`` (a linear sum — the aggregator
    contract, see ``core/aggregation.py``).

    Pre-weighted stacks (``stack.pre_weighted``: ring quantizer and/or
    masker) fold each client's aggregation-weight share into its OWN upload
    — the ring quantizer grids ``(w_i / W) * delta_i``, the float masker
    ships ``w_i * delta_i + masks`` — so the aggregate here is the
    UNWEIGHTED sum of uploads divided by ``W`` (re-weighting a masked
    upload would break mask cancellation).  On the ring path the reduced
    sum is additionally wrapped back into the centered ring (exact — each
    pair's masks sum to a multiple of ``2^b``) and decoded through the
    shared public grid scale: ``params + scale * wrap(sum of uploads)``.
    """
    stack = transforms_mod.make_stack(tcfg, scfg)
    w_cohort = weights if w_full is None else w_full
    if client_loop(params, x.shape[0]) == "scan":
        return _scan_round(params, x, y, batch_idx, weights, keys, lr,
                           prox_mu, cfg=cfg, loss=loss, stack=stack, agg=agg,
                           slots=slots, w_cohort=w_cohort,
                           round_key=round_key)
    # named scopes put the stage in each op's HLO ``op_name`` metadata
    # (``.../local_update/...``), so a device trace attributes op time to
    # its stage; they add no primitive
    with jax.named_scope("local_update"):
        locals_, client_loss = jax.vmap(
            local_update,
            in_axes=(None, 0, 0, 0, None, None, None, None))(
            params, x, y, batch_idx, lr, cfg, loss, prox_mu)
    # taint source (production no-op): per-client local models — and the
    # deltas derived from them — are the private values flcheck tracks to
    # the aggregation boundary.  client_loss is deliberately NOT tagged:
    # the weighted scalar loss release is the accepted disclosure
    # documented in docs/privacy.md.
    locals_ = taint_mod.tag_private(locals_)
    if not stack.is_identity:
        with jax.named_scope("transform"):
            deltas = jax.tree.map(lambda l, g: l - g, locals_, params)
            deltas = apply_stack(stack, deltas, keys, slots=slots,
                                 w_full=w_cohort, round_key=round_key)
    with jax.named_scope("aggregate"):
        if stack.is_identity:
            sums, wsum_local = _weighted_sums(locals_, weights)
            wsum = agg.reduce(wsum_local)
            w_agg = jax.tree.map(lambda s: agg.reduce(s) / wsum, sums)
        else:
            if stack.pre_weighted:
                # uploads already carry their weight share — sum UNWEIGHTED
                sums = jax.tree.map(lambda d: jnp.sum(d, axis=0), deltas)
            else:
                sums, _ = _weighted_sums(deltas, weights)
            wsum = agg.reduce(jnp.sum(weights))
            w_agg = _aggregate_deltas(params, sums, wsum, stack, agg,
                                      w_cohort)
        loss_mean = agg.reduce(jnp.sum(weights * client_loss)) / wsum
    return w_agg, loss_mean


def _aggregate_deltas(params, sums, wsum, stack, agg, w_cohort):
    """The new global model from the cohort's summed transformed deltas:
    ``params + sum / W``, or, for the ring quantizer, the reduced sum
    wrapped back into the centered ring (exact — each pair's masks sum to
    a multiple of ``2^b``) and decoded through the shared public grid
    scale, ``params + scale * wrap(sum)``."""
    ring = stack.ring_spec if stack.pre_weighted else None
    if ring is None:
        return jax.tree.map(lambda g, s: g + agg.reduce(s) / wsum, params,
                            sums)
    bits, sensitivity, headroom = ring
    scale = transforms_mod.ring_scale(bits, sensitivity, w_cohort.shape[0],
                                      headroom)
    return jax.tree.map(
        lambda g, s: g + scale * transforms_mod.ring_wrap(agg.reduce(s),
                                                          bits),
        params, sums)


def _scan_round(params, x, y, batch_idx, weights, keys, lr, prox_mu, *,
                cfg: ModelSpec, loss: Callable, stack, agg,
                slots, w_cohort, round_key):
    """The round with its clients one after another (:func:`client_loop`).

    Each client adds its term to the one tree they share: under the
    identity stack ``(w_i / W) * local_i``, so the shared tree ends as the
    aggregate itself (W the cohort's weight, known before the first
    client) and the round holds four parameter trees, not five: the
    global model, the aggregate, one client's copy and its gradient.  A
    transform stack's deltas are summed as the vmap path sums them and
    decoded by :func:`_aggregate_deltas`.
    """
    if slots is None:
        slots = jnp.arange(x.shape[0])
    one_client = lambda t: jax.tree.map(lambda a: a[None], t)
    with jax.named_scope("aggregate"):
        wsum = agg.reduce(jnp.sum(weights))

    def client(acc, inp):
        xi, yi, bi, wi, ki, si = inp
        with jax.named_scope("local_update"):
            local, l = local_update(params, xi, yi, bi, lr, cfg, loss,
                                    prox_mu)
        local = taint_mod.tag_private(local)       # as on the vmap path
        if stack.is_identity:
            term = jax.tree.map(lambda a: a * (wi / wsum), local)
        else:
            with jax.named_scope("transform"):
                delta = jax.tree.map(lambda a, g: a - g, local, params)
                delta = jax.tree.map(lambda a: a[0], apply_stack(
                    stack, one_client(delta), ki[None], slots=si[None],
                    w_full=w_cohort, round_key=round_key))
            term = (delta if stack.pre_weighted
                    else jax.tree.map(lambda a: a * wi, delta))
        with jax.named_scope("aggregate"):
            acc = jax.tree.map(jnp.add, acc, term)
        return acc, l

    acc, client_loss = jax.lax.scan(
        client, jax.tree.map(jnp.zeros_like, params),
        (x, y, batch_idx, weights, keys, slots))
    with jax.named_scope("aggregate"):
        if stack.is_identity:
            w_agg = jax.tree.map(agg.reduce, acc)
        else:
            w_agg = _aggregate_deltas(params, acc, wsum, stack, agg,
                                      w_cohort)
        loss_mean = agg.reduce(jnp.sum(weights * client_loss)) / wsum
    return w_agg, loss_mean


def _tree_bytes(tree) -> int:
    """Bytes of a tree's leaves (arrays, tracers or shape structs)."""
    return sum(a.size * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def _device_bytes() -> Optional[int]:
    """Memory of the first device, where its backend states it (a TPU
    does, the CPU backend does not)."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def client_loop(params, m: int) -> str:
    """How a round trains its ``m`` clients on one device: ``"vmap"`` side
    by side, ``"scan"`` one after another.

    Side by side, every client holds its local copy of the model and its
    gradient, and the aggregate its weighted share: three parameter trees
    a client beside the global model.  Where that exceeds the device's
    memory, the clients run one after another and share one sum.  Decided
    from the shapes at trace time, once per compiled round; a backend that
    states no memory (the CPU) runs side by side.
    """
    limit = _device_bytes()
    need = (3 * m + 1) * _tree_bytes(params)
    return "scan" if limit is not None and need > limit else "vmap"


@functools.partial(jax.jit,
                   static_argnames=("cfg", "loss", "tcfg", "scfg"))
def pipeline_round(params, x, y, batch_idx, weights, keys, lr, prox_mu,
                   cfg: ModelSpec, loss: Callable, tcfg: TransformConfig,
                   scfg: Optional[SecureAggConfig] = None, round_key=None):
    """Full pipeline round, pseudo-distributed (vmap) execution.

    ``keys``: (M, 2) uint32 per-client PRNG keys feeding the transform stack
    (unused — and traced away — when the stack is the identity).  With
    secure aggregation (``scfg.enabled``) the shared ``round_key`` seeds the
    pairwise masks; slots and cohort weights are the local view.  Returns
    ``(w_agg, weighted mean client loss)``; the server stage is applied by
    the caller (``RoundEngine.step``).
    """
    return _pipeline_body(params, x, y, batch_idx, weights, keys, lr, prox_mu,
                          cfg=cfg, loss=loss, tcfg=tcfg,
                          agg=aggregation_mod.LocalAggregator(), scfg=scfg,
                          round_key=round_key)


@functools.lru_cache(maxsize=None)
def make_pipeline_round(mesh, cfg: ModelSpec, loss: Callable,
                        tcfg: TransformConfig = TransformConfig(),
                        acfg: AggregationConfig = AggregationConfig(),
                        scfg: Optional[SecureAggConfig] = None):
    """Mesh-sharded pipeline round for any aggregation topology.

    The aggregator supplies both the input layout (flat: clients on a 1-D
    axis; hierarchical: leading client axis split over the 2-D
    (region, clients) grid) and the in-body collective (one psum, or
    edge->region->cloud psum pair).  lru_cached on the full execution
    geometry so every engine sharing (mesh, cfg, loss, transform, topology)
    reuses one jitted round.

    ``round_fn(params, x, y, batch_idx, weights, keys, lr, prox_mu)``.
    With a cohort-aware stack (secure aggregation, or the clear ring
    quantizer) the signature grows the cohort context —
    ``round_fn(params, x, y, batch_idx, weights, keys, slots, w_full,
    round_key, lr, prox_mu)`` — where ``slots`` (global dispatch slot ids)
    shards alongside the client data and ``w_full``/``round_key`` are
    replicated: each shard's clients mask against the WHOLE cohort, and the
    masks cancel in the cross-shard reduction.
    """
    agg = aggregation_mod.make_aggregator(acfg, mesh)
    pspec = agg.pspec()
    # the extended (slots, w_full, round_key) signature is needed whenever
    # the stack wants cohort context — masking, but also the clear ring
    # quantizer (quantize_ring without masking), whose shared grid is a
    # function of the cohort weight vector
    needs_ctx = transforms_mod.make_stack(tcfg, scfg).needs_cohort

    if not needs_ctx:
        def round_body(params, x, y, batch_idx, weights, keys, lr, prox_mu):
            return _pipeline_body(params, x, y, batch_idx, weights, keys, lr,
                                  prox_mu, cfg=cfg, loss=loss, tcfg=tcfg,
                                  agg=agg)

        return jax.jit(jax.shard_map(
            round_body, mesh=mesh,
            in_specs=(P(), pspec, pspec, pspec, pspec, pspec, P(), P()),
            out_specs=(P(), P()),
            check_vma=False))

    def secure_body(params, x, y, batch_idx, weights, keys, slots, w_full,
                    round_key, lr, prox_mu):
        return _pipeline_body(params, x, y, batch_idx, weights, keys, lr,
                              prox_mu, cfg=cfg, loss=loss, tcfg=tcfg,
                              agg=agg, scfg=scfg, round_key=round_key,
                              slots=slots, w_full=w_full)

    return jax.jit(jax.shard_map(
        secure_body, mesh=mesh,
        in_specs=(P(), pspec, pspec, pspec, pspec, pspec, pspec, P(), P(),
                  P(), P()),
        out_specs=(P(), P()),
        check_vma=False))


# ------------------------------------------------------------- round engine
class RoundEngine:
    """Composable federated round: select -> local update -> transform ->
    aggregate -> server update.

    Owns the jitted pipeline round for ONE execution path (vmap when
    ``mesh is None``, shard_map otherwise) plus the server-optimizer state,
    so round logic is unit-testable without running full training::

        engine = RoundEngine(fcfg, flcfg)          # or mesh=mesh
        params, state = engine.init(jax.random.PRNGKey(flcfg.seed))
        sel = engine.select(rng, members, m, round_idx, member_weights)
        params, state, loss = engine.step(params, state, x[sel], y[sel],
                                          bidx, counts[sel], round_idx)

    Every pluggable stage is bound from the ``FLConfig`` facade's typed
    views; hierarchical aggregation additionally requires the mesh to carry
    the (region, clients) axis pair (``aggregation.make_mesh``).
    """

    def __init__(self, fcfg: ModelSpec, flcfg: FLConfig, *,
                 loss: Optional[Callable] = None, mesh=None,
                 audited_payload: Optional[float] = None):
        # stage names/knobs were validated eagerly by the FLConfig facade
        self.fcfg, self.flcfg = fcfg, flcfg
        ccfg = flcfg.client_opt
        self.loss = loss if loss is not None else losses_mod.make_loss(
            ccfg.loss, ccfg.beta)
        self.mesh = mesh
        self.sampler = sampling_mod.make_sampler(flcfg.sampling_config)
        # proximal term only under fedprox (prox_mu is ignored otherwise)
        self.prox_mu = ccfg.prox_mu if flcfg.server_opt == "fedprox" else 0.0
        self.weighted = server_opt_mod.uses_weighted_aggregation(flcfg)
        self.transform = flcfg.transform
        # secure aggregation (pairwise masking) + privacy accounting
        self.secure = flcfg.secure if flcfg.secure.enabled else None
        # cohort-aware stack (masking and/or the shared-grid ring
        # quantizer): the round fns take the extended (slots, w_full,
        # round_key) signature
        self.needs_ctx = transforms_mod.make_stack(
            self.transform, self.secure).needs_cohort
        self.accountant: Optional[privacy_mod.PrivacyAccountant] = None
        if mesh is None:
            if flcfg.aggregation_config.kind != "flat":
                raise ValueError(
                    f"aggregation={flcfg.aggregation!r} requires a mesh "
                    "(build one with aggregation.make_mesh); the vmap path "
                    "has no reduction topology")
            self._sharded = None
            self._client_sharding = None
        else:
            self._sharded = make_pipeline_round(
                mesh, fcfg, self.loss, self.transform,
                flcfg.aggregation_config, scfg=self.secure)
            self._client_sharding = NamedSharding(
                mesh, aggregation_mod.make_aggregator(
                    flcfg.aggregation_config, mesh).pspec())
        # ---- round pacing (sync vs semi-sync buffered) -------------------
        # the latency model is host-side only: under mode="sync" it just
        # tracks a simulated wall clock and never touches the round math
        from repro.core import async_engine, latency as latency_mod
        self.async_cfg = flcfg.async_config
        # ring masking keeps the quantized wire under secure aggregation
        # (masks live in the quantizer's integer ring), so masked uploads
        # are charged their true int<b>+scale bytes whenever quantization
        # is on — the link budget no longer re-widens to fp32 for masking.
        # audited_payload (the flcheck level-3 auditor's statically derived
        # byte count, analysis/costs.py) overrides the formula when given.
        wire_bits = flcfg.quantize_bits
        self.latency = latency_mod.LatencyModel(
            self.async_cfg.latency, flcfg.seed,
            latency_mod.payload_bytes(fcfg.num_params(), wire_bits,
                                      audited_bytes=audited_payload),
            churn=flcfg.churn)
        self.async_state = async_engine.SemiSyncState()
        self._client_fn = None
        if self.async_cfg.mode == "semi_sync":
            m_prime = self.dispatch_m(flcfg.clients_per_round)
            # buffer_frac resolves per round in semi_sync_step; buffer_k is
            # absolute (0 = wait for all dispatched)
            self.buffer_k = self.async_cfg.buffer_k or m_prime
            if self.async_cfg.buffer_k > m_prime:
                raise ValueError(
                    f"buffer_k={self.buffer_k} exceeds the dispatch size "
                    f"m'={m_prime} (= ceil(over_select * clients_per_round))"
                    " — the flush could never trigger; use buffer_frac for "
                    "a threshold relative to the actual round size")
            if mesh is not None:
                self._client_fn = async_engine.make_sharded_client_deltas(
                    mesh, fcfg, self.loss, flcfg.transform,
                    flcfg.aggregation_config, scfg=self.secure)
        else:
            self.buffer_k = 0

    def dispatch_m(self, m: int, n_members: Optional[int] = None) -> int:
        """Per-round dispatch size: ``m`` under sync, the over-selected
        ``m' = ceil(over_select * m)`` (capped at the membership) under
        semi-sync."""
        if self.async_cfg.mode != "semi_sync":
            return m
        m_prime = int(np.ceil(self.async_cfg.over_select * m))
        return m_prime if n_members is None else min(m_prime, n_members)

    @property
    def sim_time(self) -> float:
        """Simulated wall-clock seconds consumed so far (event clock)."""
        return self.async_state.clock

    def reset_pacing(self) -> None:
        """Drop buffered stragglers + rewind the simulated clock (call
        between independent trainings, e.g. per cluster)."""
        self.async_state.reset()

    def init(self, key):
        """Fresh global params + server-optimizer state."""
        params = self.fcfg.init(key)
        return params, server_opt_mod.init_server_state(params,
                                                        self.flcfg.server)

    def put_clients(self, *arrays):
        """Copy client-stacked host arrays to the device(s) of this
        engine's round: on a mesh each array lands already split over the
        client axes (one transfer per shard), never whole on the first
        chip."""
        if self._client_sharding is None:
            return tuple(jnp.asarray(a) for a in arrays)
        return tuple(jax.device_put(a, self._client_sharding)
                     for a in arrays)

    def select(self, rng, members: np.ndarray, m: int, round_idx: int,
               weights: Optional[np.ndarray] = None) -> np.ndarray:
        """Pick this round's m participants (``FLConfig.sampling``)."""
        return self.sampler(rng, np.asarray(members), m, round_idx, weights)

    def base_round_key(self, round_idx: int, stream: int = 0):
        """The dispatch cohort's SHARED round key: every member can derive
        it (in a real deployment, from the round's key-agreement), and the
        pairwise secure-agg masks are a pure function of it + the slot
        pair, so clients need no pairwise communication to agree on masks.
        """
        rk = jax.random.fold_in(jax.random.PRNGKey(self.flcfg.seed), stream)
        return jax.random.fold_in(rk, round_idx)

    def rekey_key(self, round_idx: int, stream: int = 0,
                  generation: int = 0):
        """The shared cohort key at dropout-recovery generation ``g``
        (``core/async_engine._handle_timeouts``): generation 0 is the
        dispatch key itself (``base_round_key``); after a timeout the
        survivors re-mask under ``fold_in(fold_in(base, _REKEY_DOMAIN), g)``
        — derivable by every survivor from the round's key agreement, and
        domain-separated so no generation's masks collide with any dispatch
        round's."""
        rk = self.base_round_key(round_idx, stream)
        if generation == 0:
            return rk
        return jax.random.fold_in(
            jax.random.fold_in(rk, secure_agg_mod._REKEY_DOMAIN),
            generation)

    def round_keys(self, round_idx: int, m: int, stream: int = 0):
        """Per-client transform keys for one round: deterministic in
        (``FLConfig.seed``, ``stream``, round index, selection slot), so DP
        noise and stochastic rounding replay exactly under a fixed seed.

        ``stream`` decorrelates concurrent trainings sharing one seed (the
        driver passes the cluster id) — without it, two clusters' round-t
        slot-i clients would draw the SAME Gaussian noise, and the
        difference of their released aggregates would cancel the DP noise.
        """
        rk = self.base_round_key(round_idx, stream)
        return jax.vmap(jax.random.fold_in, (None, 0))(rk, jnp.arange(m))

    def attach_accountant(self, n_members: int, dispatch_m: int) -> None:
        """(Re)bind the (eps, delta) accountant for one training run:
        sampling rate ``q = dispatch_m / n_members`` (the over-selected
        dispatch size under semi-sync — those clients' data is used).
        Called by the driver per cluster; ``engine.step`` composes one
        mechanism invocation per dispatch/flush.

        Central (``central:secure-agg``) accounting of the masked sum
        (aggregate Gaussian ``z_eff = z * sqrt(cohort)`` — ``privacy.
        secure_agg_accountant``) applies only when the protocol really
        reduces the server's view to the uniform cohort sum: RING masking
        (information-theoretically hiding; float Gaussian masks are not)
        AND uniform aggregation (a weighted sum concentrates sensitivity
        on heavy clients faster than it concentrates noise).  Otherwise
        the engine falls back to per-client accounting — sound, since the
        per-client multiplier never depended on the sum — and surfaces the
        reason as ``central_fallback_reason`` in the report.
        """
        q = min(1.0, dispatch_m / max(n_members, 1))
        if self.secure is not None:
            stack = transforms_mod.make_stack(self.transform, self.secure)
            gate = privacy_mod.central_gate_reason(
                ring=stack.ring_spec is not None, weighted=self.weighted)
            if gate is None:
                self.accountant = privacy_mod.secure_agg_accountant(
                    self.transform, self.flcfg.privacy, q,
                    secure_enabled=True, cohort=dispatch_m)
                return
            self.accountant = privacy_mod.make_accountant(
                self.transform, self.flcfg.privacy, q)
            self.accountant.central_fallback_reason = gate
            return
        self.accountant = privacy_mod.make_accountant(
            self.transform, self.flcfg.privacy, q)

    def step(self, params, state, x, y, batch_idx, weights,
             round_idx: int = 0, stream: int = 0):
        """One full round on already-selected client data.

        x: (M, n_win, L, 1) and y: (M, n_win, H) windows, or x: (M, T)
        normalized series and y None (``client.minibatches``); batch_idx:
        (M, steps, B); weights: (M,) per-client sample counts — zero marks
        mesh-padding duplicates, which are excluded from aggregation AND
        loss on both the uniform and weighted paths.  ``round_idx`` /
        ``stream`` seed the per-client transform keys (only consumed when a
        transform stack is configured).  Returns ``(new params, new server
        state, round loss)``.

        Dispatches on ``FLConfig.mode``: ``sync`` (default) waits for every
        client — the round's simulated cost is the slowest client's latency;
        ``semi_sync`` routes through the staleness-weighted buffered server
        (``core/async_engine.py``), where M is the over-selected ``m'``.
        """
        if self.accountant is not None:
            # one dispatch = one subsampled-Gaussian invocation (each
            # semi-sync step dispatches one cohort and flushes once).  The
            # central accountant prices the sum at the REAL client count —
            # pads and absent members contribute no noise draw (no-op for
            # per-client accountants)
            self.accountant.observe_cohort(
                int((np.asarray(weights) > 0).sum()))
            self.accountant.step()
        if self.async_cfg.mode == "semi_sync":
            from repro.core import async_engine
            return async_engine.semi_sync_step(
                self, params, state, x, y, batch_idx, weights, round_idx,
                stream)
        # sync: the straggler gates the round — advance the simulated clock
        # by the max client latency (host-side; the round math is untouched)
        w_np = np.asarray(weights, np.float32)
        real = np.flatnonzero(w_np > 0)
        times = self.latency.times(round_idx, w_np[real],
                                   self.flcfg.client_opt.local_epochs,
                                   slots=real)
        self.async_state.clock += float(times.max(initial=0.0))
        return self._sync_step(params, state, x, y, batch_idx, weights,
                               round_idx, stream)

    def _sync_step(self, params, state, x, y, batch_idx, weights,
                   round_idx: int = 0, stream: int = 0):
        """The synchronous fused round (select-free part of paper Alg. 1);
        also the semi-sync fast path when a flush is a complete, fresh
        dispatch set (identical math — all staleness tau = 0)."""
        w = jnp.asarray(weights, jnp.float32)
        if not self.weighted:             # uniform aggregation (pads stay 0)
            w = (w > 0).astype(jnp.float32)
        lr = jnp.float32(self.flcfg.lr)
        mu = jnp.float32(self.prox_mu)
        m = x.shape[0]
        keys = self.round_keys(round_idx, m, stream)
        rk = (self.base_round_key(round_idx, stream)
              if self.needs_ctx else None)
        if self._sharded is not None:
            if self.needs_ctx:
                # slots shard with the clients; the cohort weight vector and
                # round key replicate so every shard masks vs the whole set
                w_agg, loss = self._sharded(params, x, y, batch_idx, w, keys,
                                            jnp.arange(m), w, rk, lr, mu)
            else:
                w_agg, loss = self._sharded(params, x, y, batch_idx, w, keys,
                                            lr, mu)
        else:
            w_agg, loss = pipeline_round(params, x, y, batch_idx, w, keys,
                                         lr, mu, self.fcfg, self.loss,
                                         self.transform, self.secure, rk)
        params, state = server_opt_mod.server_update(params, w_agg, state,
                                                     self.flcfg.server)
        return params, state, loss


# ------------------------------------------------------------------ driver
@dataclasses.dataclass
class FLResult:
    params: Dict                            # left on the last round's device(s)
    loss_history: np.ndarray
    cluster_centroids: Optional[np.ndarray] = None
    cluster_assignments: Optional[np.ndarray] = None  # (N,); -1 = held out
    heldout_clients: Optional[np.ndarray] = None
    sim_times: Optional[np.ndarray] = None  # (T,) simulated seconds at each
    #                                       # round's end (latency model)
    eps_history: Optional[np.ndarray] = None  # (T,) running accountant eps
    #                                       # after each round (inf when the
    #                                       # accountant is disabled)
    privacy: Optional[Dict] = None          # final accountant report
    #                                       # (core/privacy.py::report)


def time_to_target(res: FLResult, target: float) -> float:
    """Simulated seconds until ``res.loss_history`` first reaches ``target``
    — the wall-clock-to-accuracy readout for comparing round-pacing modes.
    Returns ``nan`` when the run never got there (e.g. diverged)."""
    hit = np.flatnonzero(res.loss_history <= target)
    return float(res.sim_times[hit[0]]) if len(hit) else float("nan")


def final_loss(res: FLResult) -> float:
    """Last FINITE entry of the loss history — under cohort-atomic
    semi-sync pacing (secure aggregation) a flush that completes no cohort
    records ``nan``, so drivers comparing pacing modes must anchor their
    common target here, not at ``loss_history[-1]``."""
    finite = res.loss_history[np.isfinite(res.loss_history)]
    return float(finite[-1]) if len(finite) else float("nan")


def _seed_rngs(seed: int):
    """Independent (holdout, round) rng streams.

    ``SeedSequence.spawn`` derives decorrelated child streams from one root
    seed, so the holdout permutation can NOT replay as the first round's
    client selection (which it did when both were ``default_rng(seed)``).
    """
    hold_ss, round_ss = np.random.SeedSequence(seed).spawn(2)
    return np.random.default_rng(hold_ss), np.random.default_rng(round_ss)


def _as_provider(data, fcfg: ModelSpec) -> windows.ClientWindowProvider:
    if isinstance(data, windows.ClientWindowProvider):
        return data
    # in-memory sources cache every client: the raw series are already
    # resident, so caching all N clients costs no more than the old
    # materialize-everything path did, and full-participation configs
    # (clients_per_round == N) would thrash any smaller LRU every round
    return windows.ClientWindowProvider.from_series(
        data, fcfg.lookback, fcfg.horizon, cache_size=len(data))


def _restore_async_state(flat, n_pending: int, params):
    """Rebuild a ``SemiSyncState`` from a checkpoint's flat array view
    (keys under ``cur/async/``); ``params`` supplies the delta tree
    structure (a buffered delta has exactly the param tree's shape)."""
    from repro.core import async_engine
    delta_like = jax.tree.map(np.asarray, params)
    tree = {
        "clock": flat["cur/async/clock"],
        "counters": flat["cur/async/counters"],
        "pending": [
            {"delta": jax.tree.map(
                np.asarray, checkpoint_mod.unflatten_like(
                    delta_like, flat,
                    prefix=f"cur/async/pending/{i}/delta/")),
             "scalars": flat[f"cur/async/pending/{i}/scalars"]}
            for i in range(n_pending)],
        "cohort_rounds": flat["cur/async/cohort_rounds"],
        "cohort_sizes": flat["cur/async/cohort_sizes"],
        "cohort_gens": flat["cur/async/cohort_gens"],
        "cohort_w": flat["cur/async/cohort_w"],
    }
    # dispatch-time weight sums (ring-decode geometry); absent in
    # pre-ring checkpoints — from_tree then falls back to sum(cohort_w)
    if "cur/async/cohort_W0" in flat:
        tree["cohort_W0"] = flat["cur/async/cohort_W0"]
    return async_engine.SemiSyncState.from_tree(tree)


def run_federated_training(all_series, fcfg: ModelSpec,
                           flcfg: FLConfig, *, mesh=None,
                           log_every: int = 0,
                           checkpoint_path=None, checkpoint_every: int = 1,
                           resume: bool = True,
                           stop_after_rounds: Optional[int] = None
                           ) -> Dict[int, FLResult]:
    """Full Alg. 1 via the round engine: optional client holdout, optional
    clustering, then per-cluster federated training.

    all_series: (N, T) raw kWh (one row per client), a ragged list of (T_i,)
    series, or a ``windows.ClientWindowProvider`` — everything is routed
    through the provider, so each round fetches and normalizes ONLY the
    ``m`` selected clients (host→device traffic O(m), never O(N)) and ships
    their train series, which the device windows (``client.minibatches``).
    When ``flcfg.holdout_frac > 0`` that fraction of clients is excluded from
    training entirely (unseen-client generalization split; their indices are
    reported on every ``FLResult.heldout_clients``).  Returns
    {cluster_id: FLResult}; cluster_id = -1 when clustering is off.

    **Checkpoint/resume** (``checkpoint_path``): every ``checkpoint_every``
    rounds the FULL engine state — params, server-optimizer moments, the
    semi-sync pending buffer (deltas, weights, finish times, cohort re-key
    bookkeeping), the event clock, the RDP accountant, the driver's rng —
    is written to one ``.npz``; an existing checkpoint (same config —
    enforced by fingerprint) resumes the run and reproduces the remaining
    loss/eps/sim histories BIT-identically to the uninterrupted run (pinned
    by regression test).  Holdout split, clustering, and selection replay
    deterministically from the seed, so only genuinely mutable state is
    stored.  ``stop_after_rounds`` ends the call after that many executed
    rounds (a graceful kill, for tests and budgeted jobs) — the returned
    dict then holds the partial current cluster.
    """
    provider = _as_provider(all_series, fcfg)
    holdout_rng, rng = _seed_rngs(flcfg.seed)
    if mesh is None and flcfg.aggregation_config.kind != "flat":
        # hierarchical aggregation implies mesh execution; build the
        # (region, clients) grid the config asks for over all devices
        mesh = aggregation_mod.make_mesh(flcfg.aggregation_config)
    engine = RoundEngine(fcfg, flcfg, mesh=mesh)
    ccfg = flcfg.client_opt
    steps = ccfg.local_steps or partition.local_steps(
        provider.n_win_max, ccfg.batch_size, ccfg.local_epochs)

    n_total = provider.n_clients
    train_ids, held_ids = partition.holdout_clients(
        holdout_rng, n_total, flcfg.holdout_frac)
    if len(train_ids) == 0:
        raise ValueError(
            f"holdout_frac={flcfg.holdout_frac} leaves no training clients "
            f"(n_clients={n_total})")
    # Per-client sample counts: aggregation + sampling weights.  With ragged
    # histories these differ across clients, which is exactly when
    # fedavg_weighted / weighted sampling depart from uniform.
    counts = provider.train_counts.astype(np.float32)
    n_dev = 1 if mesh is None else int(
        np.prod([mesh.shape[a] for a in mesh.axis_names]))

    # -------- optional privacy-preserving clustering (server side, Alg. 1)
    if flcfg.n_clusters > 1:
        z = provider.daily_summary(train_ids, flcfg.cluster_days)
        cents, train_assigns, _ = clustering.kmeans(z, flcfg.n_clusters,
                                                    seed=flcfg.seed)
        groups = {cid: train_ids[m] for cid, m in
                  partition.cluster_partition(train_assigns).items()}
        # report assignments in FULL client index space (-1 = held out)
        assigns = np.full(n_total, -1, train_assigns.dtype)
        assigns[train_ids] = train_assigns
    else:
        cents, assigns = None, None
        groups = {-1: train_ids}

    # -------- resume: load the full engine snapshot when one exists
    ckpt_flat = ckpt_meta = None
    if checkpoint_path is not None and resume and \
            checkpoint_mod._normalize(checkpoint_path).exists():
        ckpt_flat, ckpt_meta = checkpoint_mod.load_arrays(checkpoint_path)
        if ckpt_meta.get("flcfg") != repr(flcfg):
            raise ValueError(
                f"checkpoint {checkpoint_path} was written by a different "
                "FLConfig — resuming would silently change the run; delete "
                "it or pass resume=False")

    results: Dict[int, FLResult] = {}
    # finished clusters' accountant states: the central accountant's min
    # observed cohort is run history (churn re-keys), not derivable from
    # the configs, so resume must restore it rather than recompose from
    # the round count alone
    done_acct: Dict[int, Dict] = {}
    executed = 0

    def _save(cid, params, sstate, hist, sim_hist, eps_hist, t_done):
        tree = {
            "cur": {"params": params,
                    "server": {"m": sstate.m, "v": sstate.v, "t": sstate.t},
                    "async": engine.async_state.to_tree(),
                    "hist": np.asarray(hist, np.float64),
                    "sim": np.asarray(sim_hist, np.float64),
                    "eps": np.asarray(eps_hist, np.float64)},
            "done": {str(dc): {
                "params": results[dc].params,
                "hist": np.asarray(results[dc].loss_history, np.float64),
                "sim": np.asarray(results[dc].sim_times, np.float64),
                "eps": np.asarray(results[dc].eps_history, np.float64)}
                for dc in results},
        }
        meta = {"version": 1, "flcfg": repr(flcfg), "cluster": int(cid),
                "rounds_done": int(t_done),
                # publish generation for serving-registry pollers
                # (checkpoint.latest): the GLOBAL executed-round counter,
                # monotone across clusters, unlike per-cluster rounds_done
                "generation": int(executed),
                "done": [int(dc) for dc in results],
                "rng": rng.bit_generator.state,
                "accountant": engine.accountant.state_dict(),
                "done_accountants": {str(dc): done_acct[dc]
                                     for dc in results},
                "n_pending": len(engine.async_state.pending)}
        checkpoint_mod.save(checkpoint_path, tree, metadata=meta)

    for cid, members in groups.items():
        # fold_in, NOT PRNGKey(seed + cid): additive seeds collide across
        # runs ((seed, cid+1) == (seed+1, cid) would share every init draw)
        key = jax.random.fold_in(jax.random.PRNGKey(flcfg.seed),
                                 cid if cid >= 0 else 0)
        params, sstate = engine.init(key)
        engine.reset_pacing()          # per-cluster event clock + buffer
        hist, sim_hist, eps_hist = [], [], []
        m = min(flcfg.clients_per_round, len(members))
        # semi-sync over-selects m' >= m; sync dispatches exactly m
        m_sel = engine.dispatch_m(m, len(members))
        # (eps, delta) accounting for THIS cluster's mechanism: sampling
        # rate = dispatch size / cluster membership, stepped per flush
        engine.attach_accountant(len(members), m_sel)
        t0 = 0
        if ckpt_meta is not None and int(cid) in ckpt_meta["done"]:
            # finished before the kill: rebuild its result from the snapshot
            # (the privacy report needs the saved accountant state — the
            # central mode's min observed cohort is run history; pre-churn
            # checkpoints fall back to recomposing from the round count —
            # and centroids/holdout were recomputed above from the seed)
            pref = f"done/{cid}/"
            engine.accountant.load_state(
                ckpt_meta.get("done_accountants", {}).get(
                    str(cid), {"rounds": flcfg.rounds}))
            done_acct[cid] = engine.accountant.state_dict()
            results[cid] = FLResult(
                jax.device_get(checkpoint_mod.unflatten_like(
                    params, ckpt_flat, prefix=pref + "params/")),
                np.asarray(ckpt_flat[pref + "hist"]),
                cents, assigns, held_ids if len(held_ids) else None,
                sim_times=np.asarray(ckpt_flat[pref + "sim"]),
                eps_history=np.asarray(ckpt_flat[pref + "eps"]),
                privacy=engine.accountant.report())
            continue
        if ckpt_meta is not None and int(cid) == int(ckpt_meta["cluster"]):
            # mid-cluster kill point: restore the live engine state and the
            # driver rng, then continue the round loop where it stopped
            params = checkpoint_mod.unflatten_like(params, ckpt_flat,
                                                   prefix="cur/params/")
            sstate = server_opt_mod.ServerState(
                m=checkpoint_mod.unflatten_like(sstate.m, ckpt_flat,
                                                prefix="cur/server/m/"),
                v=checkpoint_mod.unflatten_like(sstate.v, ckpt_flat,
                                                prefix="cur/server/v/"),
                t=jnp.asarray(ckpt_flat["cur/server/t"], jnp.int32))
            engine.async_state = _restore_async_state(
                ckpt_flat, int(ckpt_meta["n_pending"]), params)
            engine.accountant.load_state(ckpt_meta["accountant"])
            rng.bit_generator.state = ckpt_meta["rng"]
            hist = [float(v) for v in ckpt_flat["cur/hist"]]
            sim_hist = [float(v) for v in ckpt_flat["cur/sim"]]
            eps_hist = [float(v) for v in ckpt_flat["cur/eps"]]
            t0 = int(ckpt_meta["rounds_done"])
        if (engine.async_cfg.mode == "semi_sync"
                and engine.async_cfg.buffer_k >= m_sel > 0
                and engine.async_cfg.buffer_k):
            # an absolute threshold the round can never fill waits for the
            # slowest straggler — legal, but the user should know
            print(f"[cluster {cid}] semi_sync: buffer_k="
                  f"{engine.async_cfg.buffer_k} >= dispatch size {m_sel} — "
                  "every flush waits for all (sync pacing); use buffer_frac "
                  "for a round-size-relative threshold")
        # mesh divisibility: round UP and pad the selection (never train
        # fewer clients than configured); pads are cycled duplicates that
        # enter the round with weight 0, so the math is unchanged
        m_run = -(-m_sel // n_dev) * n_dev
        # what fl.step runs, for the trace: the client loop each device's
        # m_run / n_dev clients take, the positions trained a round, and
        # whether the fused kernels or the scan differentiate the LSTM
        loop = client_loop(params, m_run // n_dev)
        tokens = m_run * steps * ccfg.batch_size * fcfg.lookback
        recurrence = "fused" if forecaster.fused_recurrence(fcfg) else "scan"
        stopped = False
        for t in range(t0, flcfg.rounds):
            # host spans on the profiler's clock (about a microsecond each
            # when no trace is active): a device trace attributes each idle
            # gap of the chip to the host stage that left it idle
            with jax.profiler.StepTraceAnnotation("fl.round", step_num=t):
                with jax.profiler.TraceAnnotation("fl.select"):
                    # membership churn: absent members sit this round out
                    # (pure function of (seed, round, client id) —
                    # replayable).  If the whole cluster is absent, fall
                    # back to full membership rather than dispatch nothing.
                    # Shapes stay fixed at m_run: a smaller selection just
                    # grows the zero-weight padding.
                    avail = members
                    if engine.latency.churn.absent_prob > 0.0:
                        mask = engine.latency.available(t, members)
                        if mask.any():
                            avail = members[mask]
                    sel = engine.select(rng, avail, min(m_sel, len(avail)),
                                        t, counts[avail])
                    bidx = partition.ragged_minibatch_indices(
                        rng, counts[sel], steps, ccfg.batch_size)
                    pad_idx = np.resize(np.arange(len(sel)), m_run)
                # the round ships the cohort's normalized train series,
                # L + H times fewer bytes than its windows; the device
                # windows them (client.minibatches)
                with jax.profiler.TraceAnnotation(
                        "fl.round_batch", clients=m_run,
                        layout="series") as span:
                    s, c_sel = provider.round_series(sel[pad_idx])
                    span.set_metadata(windows=int(c_sel.sum()),
                                      bytes=s.nbytes)
                w = c_sel.copy()
                w[len(sel):] = 0.0                    # mask padding clients
                with jax.profiler.TraceAnnotation("fl.put") as span:
                    put = engine.put_clients(s, bidx[pad_idx])
                    span.set_metadata(bytes=sum(a.nbytes for a in put))
                with jax.profiler.TraceAnnotation(
                        "fl.step", client_loop=loop, tokens=tokens,
                        recurrence=recurrence):
                    params, sstate, l = engine.step(
                        params, sstate, put[0], None, put[1], w, round_idx=t,
                        stream=cid if cid >= 0 else 0)
                del put          # the device inputs die with their round
                with jax.profiler.TraceAnnotation("fl.loss_sync"):
                    hist.append(float(l))
                sim_hist.append(engine.sim_time)
                eps_hist.append(engine.accountant.epsilon())
                if log_every and (t + 1) % log_every == 0:
                    eps = eps_hist[-1]
                    eps_s = f" eps {eps:.2f}" if np.isfinite(eps) else ""
                    print(f"[cluster {cid}] round {t+1}/{flcfg.rounds} "
                          f"loss {hist[-1]:.5f} sim_t {sim_hist[-1]:.1f}s"
                          f"{eps_s}")
                executed += 1
                stopped = (stop_after_rounds is not None
                           and executed >= stop_after_rounds)
                if checkpoint_path is not None and (
                        (t + 1) % max(checkpoint_every, 1) == 0
                        or t + 1 == flcfg.rounds or stopped):
                    _save(cid, params, sstate, hist, sim_hist, eps_hist,
                          t + 1)
            if stopped:
                break
        results[cid] = FLResult(params, np.array(hist),
                                cents, assigns,
                                held_ids if len(held_ids) else None,
                                sim_times=np.array(sim_hist),
                                eps_history=np.array(eps_hist),
                                privacy=engine.accountant.report())
        done_acct[cid] = engine.accountant.state_dict()
        if stopped:
            break
    return results


# ------------------------------------------------------------------ eval
@functools.partial(jax.jit, static_argnames=("cfg",))
def _predict(params, x, cfg):
    return forecaster.forecast(params, x, cfg)


class MetricAccumulator:
    """Streaming RMSE / MAPE / Accuracy (§4.5) over window batches.

    Accumulates sufficient statistics (Σ squared error, Σ APE, per-horizon
    Σ APE, counts) so million-window evaluations never hold predictions for
    more than one batch; ``result()`` matches the formerly-monolithic
    ``evaluate_global`` math exactly.  The APE epsilon is the ONE shared
    ``losses.MAPE_EPS``, pinning jnp- and np-path metric parity.
    """

    def __init__(self, horizon: int):
        self.sse = 0.0
        self.ape_sum = np.zeros(horizon, np.float64)
        self.rows = 0

    def update(self, pred: np.ndarray, y: np.ndarray):
        """pred/y: (n, H) in the space metrics should be computed in."""
        d = (pred - y).astype(np.float64)
        self.sse += float((d * d).sum())
        ape = np.abs((y - pred) /
                     np.maximum(np.abs(y), losses_mod.MAPE_EPS))
        self.ape_sum += ape.sum(axis=0, dtype=np.float64)
        self.rows += pred.shape[0]

    def result(self) -> Dict[str, float]:
        if self.rows == 0:
            raise ValueError("no evaluation windows accumulated (empty ids "
                             "or 0-client provider)")
        h = len(self.ape_sum)
        mean_ape = self.ape_sum.sum() / (self.rows * h)
        per_h = 100.0 - 100.0 * self.ape_sum / self.rows
        return {
            "rmse": float(np.sqrt(self.sse / (self.rows * h))),
            "mape": float(100.0 * mean_ape),
            "accuracy": float(np.clip(100.0 - 100.0 * mean_ape, 0, 100)),
            "per_horizon_accuracy": np.clip(per_h, 0, 100),
        }


def _predict_denorm(params, x, cfg, stats=None, batch: int = 8192):
    """Predict a flat window batch in device sub-batches; de-normalize to kWh
    when per-row (lo, hi) ``stats`` are given.  Returns (pred, y-transform).

    Sub-batches are zero-padded up to the next power of two so the jitted
    forecaster sees a bounded set of shapes (≤ log2(batch) traces total) —
    without this, ragged streamed eval presents a fresh remainder shape
    almost every client chunk and XLA recompiles per chunk.
    """
    n = x.shape[0]
    preds = []
    for i in range(0, n, batch):
        xb = x[i:i + batch]
        nb = xb.shape[0]
        nb_pad = 1 << max(nb - 1, 0).bit_length()      # next power of two
        if nb_pad > nb:
            xb = np.concatenate(
                [xb, np.zeros((nb_pad - nb,) + xb.shape[1:], xb.dtype)])
        preds.append(np.asarray(_predict(params, jnp.asarray(xb),
                                         cfg))[:nb])
    pred = np.concatenate(preds)
    if stats is None:
        return pred, lambda y: y
    return (windows.denormalize(pred, stats),
            lambda y: windows.denormalize(y, stats))


def evaluate_global(params, x_test: np.ndarray, y_test: np.ndarray,
                    cfg: ForecasterConfig, stats=None,
                    batch: int = 8192) -> Dict[str, float]:
    """Evaluate on (possibly huge) held-out window sets, streamed in batches.

    x_test: (n, L, 1); y_test: (n, H) — normalized per building.  ``stats`` is
    the per-row (lo, hi) min/max pair (broadcastable to (n, 1)); when given,
    MAPE/Accuracy are computed in DE-normalized kWh space, as the paper does —
    commercial base load keeps actual kWh well away from zero, which is what
    makes MAPE-based accuracy meaningful.
    Returns RMSE / MAPE / Accuracy (§4.5) + per-horizon accuracy (Table 4).
    """
    acc = MetricAccumulator(cfg.horizon)
    pred, to_space = _predict_denorm(params, x_test, cfg, stats, batch)
    acc.update(pred, to_space(y_test))
    return acc.result()


def evaluate_unseen_clients(params, series, cfg: ForecasterConfig,
                            batch: int = 8192, ids=None,
                            clients_per_chunk: int = 64) -> Dict[str, float]:
    """Unseen-CLIENT generalization (paper §5.4): run the full windowing
    pipeline on buildings never seen in training and score their *test*
    windows in kWh space.  ``series`` is (n_held, T) raw kWh, a ragged list,
    or a ``ClientWindowProvider`` (then ``ids`` restricts which clients to
    score).  Clients stream through in chunks, so arbitrarily large held-out
    populations evaluate in O(chunk) memory."""
    provider = _as_provider(series, cfg)
    acc = MetricAccumulator(cfg.horizon)
    for x, y, stats in provider.iter_test_flat(ids, clients_per_chunk):
        pred, to_space = _predict_denorm(params, x, cfg, stats, batch)
        acc.update(pred, to_space(y))
    return acc.result()
