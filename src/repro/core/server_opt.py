"""Pluggable server-side update rules for the federated round engine.

Each round the engine produces an aggregated client model ``w_agg`` (sample-
count-weighted average of the returned local models).  The server then treats

    g = w_global - w_agg            (the "pseudo-gradient", Reddi et al. 2021)

as a gradient estimate and applies one step of a server optimizer.  Selected
via ``FLConfig.server_opt``:

``fedavg``
    Uniform FedAvg (paper Alg. 1).  The engine aggregates with equal client
    weights and the server applies ``w <- w - server_lr * g`` (with
    ``server_lr=1`` this is exactly ``w <- w_agg``).  ``server_momentum > 0``
    turns this into FedAvgM (server momentum on the pseudo-gradient).
``fedavg_weighted``
    Same server step, but aggregation weights clients by their local sample
    counts (the classic McMahan et al. weighting for unbalanced data).
``fedprox``
    Weighted FedAvg aggregation + a proximal term ``mu/2 ||w - w_global||^2``
    added to each client's local objective (see ``core/client.py``;
    ``FLConfig.prox_mu``).  ``mu=0`` recovers FedAvg exactly.
``fedadam`` / ``fedyogi``
    Adaptive server optimizers (Reddi et al., "Adaptive Federated
    Optimization"): first/second moments of the pseudo-gradient, no bias
    correction; yogi uses the sign-damped second-moment update.  Tune
    ``server_lr`` / ``server_eps`` (paper defaults: lr ~1e-2..1, eps 1e-3).

All rules are pure pytree->pytree functions of ``(w_global, w_agg, state)``
and run *outside* the vmap / shard_map round body, so the two execution paths
share one server step (and aggregation inside the round stays one ``psum``).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig, ServerOptConfig
# canonical name list lives with the configs (eager facade validation);
# re-exported here so `server_opt.SERVER_OPTS` keeps working
from repro.configs.base import SERVER_OPTS

# opts whose aggregation weights clients by local sample count
WEIGHTED_AGG_OPTS = ("fedavg_weighted", "fedprox", "fedadam", "fedyogi")


def as_server_config(cfg: Union[FLConfig, ServerOptConfig]) -> ServerOptConfig:
    """Normalize to the typed server-update stage config (facade-friendly)."""
    return cfg.server if isinstance(cfg, FLConfig) else cfg


class ServerState(NamedTuple):
    """Server optimizer state (zeros where a rule has no such moment)."""
    m: Any                      # first moment / momentum buffer
    v: Any                      # second moment (fedadam / fedyogi)
    t: jnp.ndarray              # step count


def uses_weighted_aggregation(flcfg: Union[FLConfig, ServerOptConfig]) -> bool:
    return as_server_config(flcfg).name in WEIGHTED_AGG_OPTS


def init_server_state(params, cfg: Union[FLConfig, ServerOptConfig]
                      ) -> ServerState:
    """Zero moments for the server rule ``cfg``.  A moment the rule never
    reads is ``None`` in place of a parameter-sized tree of zeros: plain
    FedAvg keeps no moment, and at a model of a few GB the two zero trees
    would not fit beside the round."""
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
    cfg = as_server_config(cfg)
    adaptive = cfg.name in ("fedadam", "fedyogi")
    return ServerState(m=zeros() if adaptive or cfg.momentum > 0.0 else None,
                       v=zeros() if adaptive else None,
                       t=jnp.zeros((), jnp.int32))


def server_update(w_global, w_agg, state: ServerState,
                  flcfg: Union[FLConfig, ServerOptConfig]
                  ) -> Tuple[Any, ServerState]:
    """Apply one server step to the pseudo-gradient ``w_global - w_agg``.

    Accepts the flat ``FLConfig`` facade or the typed ``ServerOptConfig``
    stage view.  Returns ``(new_global_params, new_state)``.  Plain FedAvg
    at server lr 1 (``w <- w_agg``) returns the aggregate's own arrays: a
    jitted step would return a copy, one more parameter tree on the device
    while the round's arrays are still held.  Every other rule runs
    jitted, dispatched on its name at trace time (the config is static),
    so each compiles to its own minimal program.
    """
    cfg = as_server_config(flcfg)
    if (cfg.name in ("fedavg", "fedavg_weighted", "fedprox")
            and cfg.lr == 1.0 and cfg.momentum == 0.0):
        return w_agg, state._replace(t=state.t + 1)
    return _server_step(w_global, w_agg, state, flcfg)


@functools.partial(jax.jit, static_argnames=("flcfg",))
def _server_step(w_global, w_agg, state: ServerState,
                 flcfg: Union[FLConfig, ServerOptConfig]
                 ) -> Tuple[Any, ServerState]:
    cfg = as_server_config(flcfg)
    opt = cfg.name
    if opt not in SERVER_OPTS:
        raise ValueError(f"unknown server_opt {opt!r}; expected one of "
                         f"{SERVER_OPTS}")
    lr = cfg.lr
    g = jax.tree.map(lambda w, a: w - a, w_global, w_agg)
    t = state.t + 1

    if opt in ("fedavg", "fedavg_weighted", "fedprox"):
        if cfg.momentum > 0.0:             # FedAvgM
            m = jax.tree.map(lambda mm, gg: cfg.momentum * mm + gg,
                             state.m, g)
            new = jax.tree.map(lambda w, mm: w - lr * mm, w_global, m)
            return new, ServerState(m=m, v=state.v, t=t)
        new = jax.tree.map(lambda w, gg: w - lr * gg, w_global, g)
        return new, ServerState(m=state.m, v=state.v, t=t)

    # adaptive rules (Reddi et al. 2021, no bias correction)
    b1, b2, eps = cfg.beta1, cfg.beta2, cfg.eps
    m = jax.tree.map(lambda mm, gg: b1 * mm + (1 - b1) * gg, state.m, g)
    if opt == "fedadam":
        v = jax.tree.map(lambda vv, gg: b2 * vv + (1 - b2) * gg * gg,
                         state.v, g)
    else:                                  # fedyogi: sign-damped v update
        v = jax.tree.map(
            lambda vv, gg: vv - (1 - b2) * gg * gg * jnp.sign(vv - gg * gg),
            state.v, g)
    new = jax.tree.map(lambda w, mm, vv: w - lr * mm / (jnp.sqrt(vv) + eps),
                       w_global, m, v)
    return new, ServerState(m=m, v=v, t=t)
