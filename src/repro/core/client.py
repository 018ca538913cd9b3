"""Local-update stage of the federated pipeline (paper Alg. 1,
``ClientUpdate``): select -> **local-update** -> transform -> aggregate ->
server-update.

E epochs of minibatch SGD on the client's private windows, expressed as a
fixed-shape ``lax.scan`` over precomputed minibatch indices so that the whole
client population can be vmapped / shard_mapped over the ``clients`` axis —
the TPU-native realization of "clients train in parallel".  The stage's
schedule knobs (lr, E, B, loss, prox_mu) are carried by the typed
``configs.base.ClientOptConfig`` (the ``FLConfig.client_opt`` facade view);
the traced per-round values (lr, prox_mu) arrive as arguments so one jitted
round serves every schedule.

FedProx (Li et al. 2020) is supported via ``prox_mu``: the local objective
gains ``mu/2 ||w - w_global||^2`` anchored at the round's incoming global
params, realized as an extra ``mu * (w - w_global)`` gradient term.  With
``mu = 0`` the added term is exactly zero, so FedAvg semantics (and numerics)
are unchanged.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ForecasterConfig
from repro.models import forecaster


def sgd_step(params, batch, lr, cfg: ForecasterConfig, loss: Callable,
             cell_impl: str = "jnp", anchor=None, prox_mu=0.0):
    """One SGD step; ``anchor``/``prox_mu`` add the FedProx proximal gradient."""
    l, g = jax.value_and_grad(forecaster.loss_fn)(params, batch, cfg, loss,
                                                  cell_impl)
    if anchor is not None:
        g = jax.tree.map(lambda gw, w, a: gw + prox_mu * (w - a),
                         g, params, anchor)
    params = jax.tree.map(lambda w, gw: w - lr * gw, params, g)
    return params, l


def minibatches(x, y, cfg: ForecasterConfig) -> Callable:
    """``take(idx)``: the windows ``idx`` (B,) of one client's data as
    ``{"x": (B, L, 1), "y": (B, H)}``, from either form of the data.

    Window form: x (n_win, L, 1) and y (n_win, H), row-gathered.  Series
    form (``y is None``): x is the client's (T,) normalized series, window
    k its ``L + H`` values from k.  The series is windowed here, once per
    round and outside the step loop, into an (n_win, L + H) tensor that
    each step row-gathers: a TPU gathers whole rows natively, but lowers a
    gather of unaligned ``L + H``-wide slices of the series itself to a
    serial loop over the slices, and one of single elements runs slower
    than the row gather too.
    """
    if y is not None:
        return lambda idx: {"x": x[idx], "y": y[idx]}
    lb, width = cfg.lookback, cfg.lookback + cfg.horizon
    n = x.shape[0] - width + 1
    win = jnp.stack([x[q:q + n] for q in range(width)], axis=-1)

    def take(idx):
        w = win[idx]
        return {"x": w[:, :lb, None], "y": w[:, lb:]}
    return take


@functools.partial(jax.jit, static_argnames=("cfg", "loss", "cell_impl"))
def local_update(params, x, y, batch_idx, lr, cfg: ForecasterConfig,
                 loss: Callable, cell_impl: str = "jnp", prox_mu=0.0):
    """Run the client's local schedule.

    params: global model (pytree); x: (n_win, L, 1) and y: (n_win, H), or
    x: (T,) normalized series and y None (see :func:`minibatches`);
    batch_idx: (steps, B) int32; prox_mu: FedProx strength (0 = plain FedAvg).
    Returns (local params, mean local loss).
    """
    anchor = params                      # round-start global model (FedProx)
    take = minibatches(x, y, cfg)

    def step(p, idx):
        return sgd_step(p, take(idx), lr, cfg, loss, cell_impl,
                        anchor=anchor, prox_mu=prox_mu)

    params, losses = jax.lax.scan(step, params, batch_idx)
    return params, jnp.mean(losses)
