"""Local-SGD / DiLoCo-style periodic aggregation — the paper's FedAvg schedule
as a *scalable cross-pod training feature* (DESIGN.md §2).

Observation: FedAvg ≡ local SGD with an H-step communication period.  On a
multi-pod mesh we exploit it where the links are slowest: gradients are
all-reduced every step only WITHIN a pod (fast ICI); parameters are averaged
ACROSS pods (slow inter-pod links) only every H inner steps, optionally passed
through an outer Nesterov optimizer (DiLoCo).  This divides the cross-pod
collective-bytes term of the roofline by ~H.

Usage inside a pjit/shard_map program over mesh ("pod", "data", "model"):

    inner:  grads = psum(grads, ("data",))          # NOT "pod"
    every H steps:
            params = outer_step(anchor, params, outer_state, axis="pod")
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



@dataclasses.dataclass(frozen=True)
class LocalSGDConfig:
    inner_steps: int = 20          # H: steps between cross-pod syncs
    outer_lr: float = 0.7          # DiLoCo outer learning rate
    outer_momentum: float = 0.9    # Nesterov momentum on the outer delta
    nesterov: bool = True


class OuterState(NamedTuple):
    anchor: Any                    # params at the last sync (the "global" model)
    momentum: Any                  # outer momentum buffer


def init_outer_state(params) -> OuterState:
    return OuterState(anchor=params,
                      momentum=jax.tree.map(jnp.zeros_like, params))


def outer_step(params, state: OuterState, cfg: LocalSGDConfig,
               axis: str = "pod") -> Tuple[Any, OuterState]:
    """Cross-pod sync: average the per-pod parameter drift and apply it to the
    anchor with an outer Nesterov optimizer.  Must run inside shard_map with
    ``axis`` bound.  With outer_lr=1, momentum=0 this is exactly FedAvg over
    pods (paper Alg. 1 line: w ← mean(w_i))."""
    delta = jax.tree.map(lambda p, a: a - p, params, state.anchor)  # anchor - local
    delta = jax.tree.map(lambda d: jax.lax.pmean(d, axis), delta)
    m = jax.tree.map(
        lambda mom, d: cfg.outer_momentum * mom + d, state.momentum, delta)
    if cfg.nesterov:
        upd = jax.tree.map(lambda mom, d: cfg.outer_momentum * mom + d, m, delta)
    else:
        upd = m
    new_anchor = jax.tree.map(lambda a, u: a - cfg.outer_lr * u,
                              state.anchor, upd)
    return new_anchor, OuterState(anchor=new_anchor, momentum=m)


def fedavg_outer(params, axis: str = "pod"):
    """Plain FedAvg across pods (outer_lr=1, no momentum)."""
    return jax.tree.map(lambda p: jax.lax.pmean(p, axis), params)


def make_sharded_outer(mesh, cfg: LocalSGDConfig, axis: str = "pod"):
    """Jitted cross-pod sync: ``sync(stacked_local_params, outer_state) ->
    (new_anchor, new_state)``.

    ``stacked_local_params`` carries one (possibly divergent) parameter tree
    per pod on a leading axis of size ``mesh.shape[axis]``; that axis is
    sharded over ``axis`` so each pod sees only its own slice, and the
    cross-pod ``pmean`` inside :func:`outer_step` does the actual averaging.
    The outer state and returned anchor are replicated."""
    def body(stacked_local_params, state):
        mine = jax.tree.map(lambda w: w[0], stacked_local_params)
        return outer_step(mine, state, cfg, axis)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                                 out_specs=(P(), P()), check_vma=False))
