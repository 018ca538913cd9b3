"""Aggregate stage of the federated pipeline (select -> local-update ->
transform -> **aggregate** -> server-update): pluggable cross-client
reduction topologies behind one tiny protocol.

An :class:`Aggregator` owns (a) the ``PartitionSpec`` that lays the
client-stacked round inputs out over the mesh and (b) the collective that
turns per-shard weighted sums into the global sum inside the round body.
The weighting math itself lives in ``core/fedavg.py::_weighted_sums`` and is
shared by every topology.

``flat`` (:class:`FlatAggregator`)
    The paper's §5.4 deployment collapsed to one collective: clients on a 1-D
    ``clients`` mesh axis, aggregation = a single ``psum`` of the (tiny)
    parameter tree — edge->cloud upload + cloud aggregation in one step.
``hierarchical`` (:class:`HierarchicalAggregator`)
    Two-level edge->region->cloud reduction over a 2-D ``(region, clients)``
    mesh: each region psums its own clients first (the regional edge
    aggregator — a Pi cluster head in the paper's §5.4 deployment), then one
    psum across regions combines the regional partials at the cloud.  Per-link
    traffic drops from N uploads into one cloud ingress to ``N/R`` per region
    + R partials upstream.  Because every per-client transform runs BEFORE the
    collective, the two topologies compute the same sum — identical to the
    flat path up to float summation order, bitwise when the reduction orders
    coincide.
``local`` (:class:`LocalAggregator`)
    The no-mesh (vmap, pseudo-distributed) execution path, where per-shard
    sums are already global: the collective is the identity.

**Linearity contract (mask cancellation).**  ``reduce`` MUST be a plain
linear sum of the per-shard values (psum / psum-of-psums / identity) —
no clipping, averaging, or reordering beyond float summation order.  The
secure-aggregation stage (``core/secure_agg.py``) relies on this: every
client's upload carries antisymmetric pairwise masks (``mask_ij =
-mask_ji``) whose sum over the dispatch cohort is zero, so the masks
cancel in ``reduce`` no matter how the cohort is sharded — each pair's two
halves may land on different shards (flat), different regions
(hierarchical), or the same vmap lane, and the cancellation is identical
up to float summation order (pinned by tests/test_privacy.py on all three
topologies).  An aggregator that broke linearity (e.g. a trimmed-mean
topology) would need masking disabled — validate eagerly if you add one.

This seam is what turns the remaining ROADMAP items into new
``Aggregator`` implementations rather than engine rewrites.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

from repro.analysis import taint
from repro.configs.base import AGGREGATORS, AggregationConfig, FLConfig

PyTree = Any


class Aggregator(Protocol):
    """Reduction topology for the aggregate stage."""

    @property
    def mesh_axes(self) -> Tuple[str, ...]:
        """Mesh axis names this topology reduces over (() = no mesh)."""
        ...

    def pspec(self) -> Optional[P]:
        """PartitionSpec sharding the leading (client) axis of round inputs."""
        ...

    def reduce(self, x: jax.Array) -> jax.Array:
        """Sum one per-shard array across all client shards.

        Must be a LINEAR sum (see the module's mask-cancellation contract):
        secure-aggregation masks cancel in this reduction.
        """
        ...


@dataclasses.dataclass(frozen=True)
class LocalAggregator:
    """vmap execution: sums are already global, the collective is identity."""

    @property
    def mesh_axes(self) -> Tuple[str, ...]:
        return ()

    def pspec(self) -> Optional[P]:
        return None

    def reduce(self, x):
        # identity collective, but still THE cross-client boundary of the
        # vmap path — flcheck checks sanitization here (production no-op)
        return taint.boundary(x)


@dataclasses.dataclass(frozen=True)
class FlatAggregator:
    """One-psum cloud aggregation over a 1-D ``clients`` mesh axis."""
    client_axis: str = "clients"

    @property
    def mesh_axes(self) -> Tuple[str, ...]:
        return (self.client_axis,)

    def pspec(self) -> P:
        return P(self.client_axis)

    def reduce(self, x):
        return jax.lax.psum(taint.boundary(x), self.client_axis)


@dataclasses.dataclass(frozen=True)
class HierarchicalAggregator:
    """Two-level edge->region->cloud reduction on a 2-D (region, clients) mesh.

    Round inputs shard their leading client axis over BOTH mesh axes
    (``P((region, clients))``); the reduction is a psum within each region
    (edge aggregation) followed by a psum across regions (cloud aggregation).
    """
    region_axis: str = "region"
    client_axis: str = "clients"

    @property
    def mesh_axes(self) -> Tuple[str, ...]:
        return (self.region_axis, self.client_axis)

    def pspec(self) -> P:
        return P((self.region_axis, self.client_axis))

    def reduce(self, x):
        regional = jax.lax.psum(taint.boundary(x),
                                self.client_axis)        # edge -> region
        return jax.lax.psum(regional, self.region_axis)  # region -> cloud


def make_aggregator(cfg: Union[FLConfig, AggregationConfig, str, None],
                    mesh=None) -> Aggregator:
    """Resolve the aggregate stage: config (or kind name) + mesh -> Aggregator.

    ``mesh=None`` always yields the :class:`LocalAggregator` (vmap path).
    With a mesh, the topology's axis names are validated against the mesh's
    eagerly, so a flat engine handed a 2-D mesh (or vice versa) fails at
    construction, not inside the jitted round.
    """
    if cfg is None:
        cfg = AggregationConfig()
    elif isinstance(cfg, FLConfig):
        cfg = cfg.aggregation_config
    elif isinstance(cfg, str):
        cfg = AggregationConfig(kind=cfg)

    if mesh is None:
        return LocalAggregator()
    agg: Aggregator = (FlatAggregator() if cfg.kind == "flat"
                       else HierarchicalAggregator())
    missing = [a for a in agg.mesh_axes if a not in mesh.axis_names]
    if missing or len(mesh.axis_names) != len(agg.mesh_axes):
        raise ValueError(
            f"{cfg.kind!r} aggregation needs mesh axes {agg.mesh_axes}, got "
            f"mesh axes {tuple(mesh.axis_names)} — build the mesh with "
            f"aggregation.make_mesh(cfg) or jax.make_mesh")
    return agg


def make_mesh(cfg: Union[AggregationConfig, FLConfig, None] = None,
              devices=None):
    """Build the device mesh an ``AggregationConfig`` asks for.

    Flat -> 1-D ``(clients,)`` over all devices.  Hierarchical -> 2-D
    ``(region, clients)`` with ``n_regions`` region groups (``n_regions=0``
    picks the largest divisor of the device count that is <= sqrt(devices),
    so an 8-device host becomes the 2x4 edge/region grid).  ``devices``
    (default: all of them) picks the devices the mesh spans.
    """
    if cfg is None:
        cfg = AggregationConfig()
    elif isinstance(cfg, FLConfig):
        cfg = cfg.aggregation_config
    n_dev = len(jax.devices() if devices is None else devices)
    if cfg.kind == "flat":
        return jax.make_mesh((n_dev,), ("clients",), devices=devices)
    r = cfg.n_regions
    if r == 0:
        r = max(d for d in range(1, int(n_dev ** 0.5) + 1) if n_dev % d == 0)
    if n_dev % r:
        raise ValueError(f"n_regions={r} does not divide device count "
                         f"{n_dev}")
    return jax.make_mesh((r, n_dev // r), ("region", "clients"),
                         devices=devices)
