"""Delta-transform stage of the federated pipeline (select -> local-update ->
**transform(deltas)** -> aggregate -> server-update).

Each transform is a pure function of ONE client's update delta
``w_i - w_global`` (a pytree) plus a per-client PRNG key, applied INSIDE the
round body (vmapped over the client axis, before the aggregation collective) —
so on the mesh path the deltas that cross the wire are already clipped /
noised / quantized, exactly like a real edge deployment where the raw local
model never leaves the device.

Knob -> literature map (see PAPERS.md):

``TransformConfig.clip_norm`` (C)
    Per-client L2 clip ``delta * min(1, C / ||delta||_2)`` — the sensitivity
    bound of DP-FedAvg, and the clip step of privacy-preserving DER
    forecasting (arXiv:2107.03248); also tames client drift on non-IID load
    data.  The ROADMAP "secure-agg / DP hooks" item plugs in here.
``TransformConfig.noise_multiplier`` (z)
    Gaussian mechanism: add ``N(0, (z*C)^2)`` per coordinate to the clipped
    delta (C falls back to 1 when clipping is off).  With clip + noise the
    per-round release is the standard Gaussian-mechanism privitization of
    each client's contribution (arXiv:2107.03248 §III).
``TransformConfig.quantize_bits`` (b)
    Stochastic b-bit integer quantize/dequantize (per-leaf max-abs scaling,
    unbiased stochastic rounding).  Models the int8 uplink compression that
    lightweight FL for load forecasting uses to cut edge upload cost
    (arXiv:2404.03320) — b=8 is a 4x wire reduction vs float32.  We simulate
    the wire format (quantize then dequantize) so aggregation math stays in
    float.

``TransformConfig.quantize_ring``
    Shared-grid RING quantizer — the wire format secure aggregation masks
    in (forced on whenever masking + quantization are both enabled, and
    available standalone as the bit-exact clear comparator).  Instead of a
    per-leaf data-dependent scale, every cohort member quantizes its
    cohort-normalized weighted contribution ``(w_i / W) * delta_i`` onto
    ONE public grid ``s = sensitivity / levels`` (sensitivity = clip norm,
    falling back to 1), with
    ``levels = floor((2^(b-1) - 1 - M) / (1 + 4z))`` reserving ``M`` grid
    steps of stochastic-rounding headroom plus a 4-sigma margin for the DP
    noise tail so the cohort's integer sum provably fits the ring without
    truncating the Gaussian.  The output is the integer grid itself (not a
    dequantized float): the aggregator sums uploads UNWEIGHTED, reduces the
    sum into the ring, and rescales — see ``fedavg._pipeline_body``.

``SecureAggConfig.enabled``
    Pairwise masking (``core/secure_agg.py``): antisymmetric per-pair masks
    derived from the cohort's shared round key, added LAST in the stack so
    the upload that crosses the wire is individually noise but the masks
    cancel in the aggregator sum — actual secure aggregation on top of the
    DP/compression stack.  With the quantize stage on the masker operates
    in the ring mod ``2^b`` (uniform integer masks, exact wraparound
    cancellation); otherwise it adds Gaussian masks to the weighted float
    upload.  It is a *cohort-aware* transform: the stack threads it a
    :class:`~repro.core.secure_agg.CohortContext` (own slot, cohort
    weights, shared round key) in addition to the per-client key.

Transforms compose as a :class:`TransformStack` in the fixed order
clip -> noise -> quantize -> mask (sensitivity bound first, privacy second,
compression third, wire masking last).  The empty stack is the identity:
``core/fedavg.py`` then aggregates the raw local models as their weighted
mean, with no delta taken.
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import taint
from repro.configs.base import SecureAggConfig, TransformConfig

PyTree = Any


class DeltaTransform(Protocol):
    """One per-client delta transform: ``(delta_tree, key) -> delta_tree``.

    Implementations must be hashable (frozen dataclasses) so a stack can be
    a static jit argument, and must be vmap-safe (pure jnp + jax.random).
    ``tag`` is the transform's STABLE key-derivation id (see
    :class:`TransformStack`): unique per transform kind, never reused.
    """

    tag: ClassVar[int]

    def __call__(self, delta: PyTree, key: jax.Array) -> PyTree: ...


def global_l2_norm(tree: PyTree) -> jax.Array:
    """L2 norm over ALL leaves of a pytree (one client's delta)."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


# ------------------------------------------------------------ ring helpers
# Noise-tail margin of the shared ring grid, in per-coordinate noise
# standard deviations: a noised contribution is kept un-truncated out to
# this many sigma (residual clipped mass 2*Phi(-4) ~ 6e-5 per coordinate).
RING_NOISE_TAIL_SIGMAS: float = 4.0


def ring_levels(bits: int, cohort: int, noise_headroom: float = 0.0) -> int:
    """Grid levels of the shared ring quantizer:
    ``floor((2^(bits-1) - 1 - M) / (1 + noise_headroom))``.

    The ``M`` reserved steps are stochastic-rounding headroom — each cohort
    member's rounding can overshoot its weight share by at most one grid
    step.  ``noise_headroom`` (``RING_NOISE_TAIL_SIGMAS * z`` when the DP
    noise stage is on, else 0) additionally reserves a multiplicative
    noise-tail margin: client ``i``'s per-coordinate Gaussian noise has std
    ``frac_i * z * levels`` grid steps, so its cap grows to
    ``frac_i * levels * (1 + noise_headroom)`` — signal plus
    ``RING_NOISE_TAIL_SIGMAS`` sigma of noise.  Without the margin the cap
    would truncate the noise at ~``1/z`` sigma, biasing the aggregate and
    voiding the full-std Gaussian premise the DP accountant prices.  The
    cohort's integer sum stays bounded by
    ``levels * (1 + noise_headroom) + M <= 2^(bits-1) - 1``, so the ring
    decode ``wrap(sum)`` is exact, never an aliased wraparound.
    """
    levels = int((2 ** (bits - 1) - 1 - int(cohort))
                 / (1.0 + float(noise_headroom)))
    if levels < 1:
        raise ValueError(
            f"dispatch cohort of {cohort} does not fit the int{bits} ring "
            f"with noise headroom {float(noise_headroom):.3g}: need "
            f"(2^{bits - 1} - 1 - cohort) / (1 + headroom) >= 1 — widen "
            "the quantize bits or lower dp_noise")
    return levels


def ring_scale(bits: int, sensitivity: float, cohort: int,
               noise_headroom: float = 0.0) -> float:
    """Public grid step of the shared ring quantizer (one float for the
    whole cohort — the +4-byte wire scale field, and the only residual
    metadata a masked upload carries)."""
    return float(sensitivity) / ring_levels(bits, cohort, noise_headroom)


def ring_wrap(x, bits: int):
    """Reduce integer-valued ``x`` into the centered ring
    ``[-2^(bits-1), 2^(bits-1) - 1]`` (i.e. mod ``2^bits``).  Exact for
    float32-encoded integers below 2^24 — the simulation's stand-in for
    int arithmetic that overflows by construction."""
    half = float(2 ** (bits - 1))
    return jnp.mod(x + half, float(2 ** bits)) - half


@dataclasses.dataclass(frozen=True)
class L2Clip:
    """Scale the whole delta so its global L2 norm is at most ``clip_norm``."""
    clip_norm: float
    tag: ClassVar[int] = 0             # stable PRNG stream id (no randomness)

    def __call__(self, delta: PyTree, key: jax.Array) -> PyTree:
        norm = global_l2_norm(delta)
        factor = jnp.minimum(1.0, self.clip_norm / jnp.maximum(norm, 1e-12))
        # taint marker (production no-op): this stage's flcheck label
        return taint.declassify(jax.tree.map(lambda x: x * factor, delta),
                                "clip")


@dataclasses.dataclass(frozen=True)
class GaussianNoise:
    """Add per-coordinate ``N(0, sigma^2)`` noise (Gaussian mechanism)."""
    sigma: float
    tag: ClassVar[int] = 1             # stable PRNG stream id

    def __call__(self, delta: PyTree, key: jax.Array) -> PyTree:
        leaves, treedef = jax.tree.flatten(delta)
        keys = jax.random.split(key, len(leaves))
        noised = [x + self.sigma * jax.random.normal(k, x.shape, x.dtype)
                  for x, k in zip(leaves, keys)]
        # taint marker (production no-op): this stage's flcheck label
        return taint.declassify(jax.tree.unflatten(treedef, noised), "noise")


@dataclasses.dataclass(frozen=True)
class StochasticQuantize:
    """Unbiased ``bits``-bit integer quantization, two grids:

    *Adaptive (default, ``ring=False``)*: each leaf is scaled by
    ``max|x| / (2^(bits-1) - 1)`` to the signed integer grid, stochastically
    rounded (``floor(x/s + u)``, ``u ~ U[0,1)`` — exact in expectation),
    then dequantized.  Round-trip error is bounded by one grid step ``s``
    per coordinate; an all-zero leaf round-trips to zero.

    *Ring (``ring=True``, cohort-aware)*: every cohort member quantizes its
    cohort-normalized weighted contribution ``(w_i / W) * x`` onto ONE
    public grid ``s = sensitivity / ring_levels(bits, M, noise_headroom)``
    and returns the INTEGER grid values themselves (float32-encoded ints),
    clipped to this client's widened weight share
    ``floor((w_i/W) * levels * (1 + noise_headroom)) + 1`` — the
    per-client cap that bounds the cohort's integer sum inside the ring
    while leaving ``RING_NOISE_TAIL_SIGMAS`` sigma of room for the DP
    noise tail (``noise_headroom = RING_NOISE_TAIL_SIGMAS * z``; see
    ``ring_levels``).  This is the grid secure-agg masks live on
    (``core/secure_agg.py``); the aggregator decodes with ``ring_wrap`` +
    ``ring_scale`` (``fedavg._pipeline_body``).  A data-INdependent grid
    means the wire scale leaks only the configured clip bound, not any
    client's delta magnitude.
    """
    bits: int = 8
    ring: bool = False
    sensitivity: float = 1.0           # ring grid bound (clip norm, or 1)
    noise_headroom: float = 0.0        # ring noise-tail margin (k * z)
    tag: ClassVar[int] = 2             # stable PRNG stream id

    @property
    def needs_cohort(self) -> bool:
        return self.ring               # ring grid needs (slot, weights)

    def __call__(self, delta: PyTree, key: jax.Array, ctx=None) -> PyTree:
        leaves, treedef = jax.tree.flatten(delta)
        keys = jax.random.split(key, len(leaves))
        out = []
        if self.ring:
            levels = ring_levels(self.bits, ctx.weights.shape[0],
                                 self.noise_headroom)
            scale = self.sensitivity / levels
            w = ctx.weights
            frac = w[ctx.slot] / jnp.maximum(jnp.sum(w), 1e-30)
            # widened cap: weight share plus the reserved noise-tail margin
            cap = float(levels) * (1.0 + self.noise_headroom)
            qmax = jnp.floor(frac * cap) + 1.0
            for x, k in zip(leaves, keys):
                u = jax.random.uniform(k, x.shape)
                q = jnp.clip(jnp.floor(frac * x / scale + u), -qmax, qmax)
                out.append(q.astype(x.dtype))
        else:
            levels = float(2 ** (self.bits - 1) - 1)   # int8 -> 127
            for x, k in zip(leaves, keys):
                sc = jnp.max(jnp.abs(x)) / levels
                safe = jnp.maximum(sc, jnp.finfo(jnp.float32).tiny)
                u = jax.random.uniform(k, x.shape)
                q = jnp.clip(jnp.floor(x / safe + u), -levels, levels)
                out.append((q * safe).astype(x.dtype))
        # taint marker (production no-op): this stage's flcheck label.  The
        # wire declaration is what the level-3 cost auditor reads off the
        # boundary: the values above STAND FOR an int<bits> grid + one fp32
        # scale per leaf on the real uplink (adaptive: simulated-dequantize
        # floats; ring: the shared-grid integers themselves).
        return taint.declassify(jax.tree.unflatten(treedef, out), "quantize",
                                wire=f"int{self.bits}+scale")


@dataclasses.dataclass(frozen=True)
class TransformStack:
    """Ordered composition of delta transforms; hashable, so jit-static.

    Each stage gets a decorrelated sub-key ``fold_in(key, t.tag)`` of the
    per-client key, so noise and stochastic rounding never share bits.  The
    fold-in uses the transform's STABLE per-kind ``tag`` — NOT its position
    in the stack — so toggling one stage (e.g. turning ``clip_norm`` off)
    cannot silently shift another stage's random stream: a DP-noise draw is
    the same bits with or without clipping/quantization around it.

    Cohort-aware transforms (``needs_cohort = True``, e.g. the pairwise
    masker) additionally receive the cohort context — calling a stack that
    contains one without ``ctx`` raises, so a secure-agg stack can never
    silently run unmasked.
    """
    transforms: Tuple[DeltaTransform, ...] = ()

    @property
    def is_identity(self) -> bool:
        return not self.transforms

    @property
    def needs_cohort(self) -> bool:
        """True when any member transform needs the dispatch-cohort context
        (slot / weights / shared round key) — see ``core/secure_agg.py``."""
        return any(getattr(t, "needs_cohort", False) for t in self.transforms)

    @property
    def ring_spec(self):
        """``(bits, sensitivity, noise_headroom)`` of the shared-grid ring
        quantizer when the stack carries one, else None — the engine's
        signal to decode the aggregate with ``ring_wrap``/``ring_scale``
        (the decode grid must be sized with the SAME noise headroom the
        encoder reserved)."""
        for t in self.transforms:
            if isinstance(t, StochasticQuantize) and t.ring:
                return (t.bits, t.sensitivity, t.noise_headroom)
        return None

    @property
    def pre_weighted(self) -> bool:
        """True when uploads already carry their aggregation weight — the
        ring quantizer folds in ``w_i / W``, the masker folds in ``w_i``
        (weighted-contribution masking) — so the aggregator must sum them
        UNWEIGHTED (weighting twice would double-count)."""
        return self.ring_spec is not None or any(
            getattr(t, "is_masker", False) for t in self.transforms)

    def __call__(self, delta: PyTree, key: jax.Array, ctx=None) -> PyTree:
        seen: dict = {}
        for t in self.transforms:
            occ = seen.get(t.tag, 0)   # same-kind repeats get fresh streams
            seen[t.tag] = occ + 1
            sub = jax.random.fold_in(jax.random.fold_in(key, t.tag), occ)
            if getattr(t, "needs_cohort", False):
                if ctx is None:
                    raise ValueError(
                        f"{type(t).__name__} needs the dispatch-cohort "
                        "context (slot/weights/round key); call the stack "
                        "with ctx=CohortContext(...)")
                delta = t(delta, sub, ctx)
            else:
                delta = t(delta, sub)
        return delta


def make_stack(cfg: TransformConfig,
               secure: Optional[SecureAggConfig] = None) -> TransformStack:
    """Build the clip -> noise -> quantize -> mask stack selected by a
    ``TransformConfig`` (+ optional ``SecureAggConfig``), the
    ``FLConfig.transform`` / ``FLConfig.secure`` facade views."""
    ts = []
    secure_on = secure is not None and secure.enabled
    sensitivity = cfg.clip_norm if cfg.clip_norm > 0.0 else 1.0
    # masking + quantization compose in the quantizer's integer ring: the
    # shared-grid ring quantizer is forced on so the masks have an integer
    # grid to be uniform over (and the wire stays int<b>+scale)
    ring = bool(cfg.quantize_bits) and (cfg.quantize_ring or secure_on)
    if cfg.clip_norm > 0.0:
        ts.append(L2Clip(cfg.clip_norm))
    if cfg.noise_multiplier > 0.0:
        ts.append(GaussianNoise(cfg.noise_multiplier * sensitivity))
    if cfg.quantize_bits:
        ts.append(StochasticQuantize(
            cfg.quantize_bits, ring=ring,
            sensitivity=sensitivity if ring else 1.0,
            # ring grids reserve k-sigma of room for the DP noise tail so
            # the per-client cap does not truncate the Gaussian (which
            # would bias the sum and void the accountant's premise)
            noise_headroom=(RING_NOISE_TAIL_SIGMAS * cfg.noise_multiplier
                            if ring else 0.0)))
    if secure_on:
        from repro.core import secure_agg  # late: secure_agg is a leaf module
        ts.append(secure_agg.make_masker(
            secure, ring_bits=cfg.quantize_bits if ring else 0))
    return TransformStack(tuple(ts))
