"""Local-update stage of the federated pipeline (paper Alg. 1,
``ClientUpdate``): select -> **local-update** -> transform -> aggregate ->
server-update.

E epochs (or K steps) of minibatch SGD on the client's private windows,
expressed as a fixed-shape ``lax.scan`` over precomputed minibatch indices
so that the whole client population can be vmapped / shard_mapped over the
``clients`` axis — the TPU-native realization of "clients train in
parallel".  The model is a ``configs.base.ModelSpec`` (its init, loss and
batch layout), so every model the engine trains shares this stage; how
the model differentiates its recurrence is the model's own choice
(``models/forecaster.py::fused_recurrence``), not this stage's.  The
stage's schedule knobs (lr, E, K, B, loss, prox_mu) are carried by the
typed ``configs.base.ClientOptConfig`` (the ``FLConfig.client_opt`` facade
view); the traced per-round values (lr, prox_mu) arrive as arguments so one
jitted round serves every schedule.

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

from repro.configs.base import ModelSpec

# widest window that is pre-windowed (see ``minibatches``)
PREWINDOW_MAX_WIDTH = 64


def sgd_step(params, batch, lr, spec: ModelSpec, loss: Callable,
             anchor=None, prox_mu=0.0):
    """One SGD step; ``anchor``/``prox_mu`` add the FedProx proximal gradient."""
    l, g = jax.value_and_grad(spec.loss)(params, batch, loss)
    if anchor is not None:
        g = jax.tree.map(lambda gw, w, a: gw + prox_mu * (w - a),
                         g, params, anchor)
    params = jax.tree.map(lambda w, gw: w - lr * gw, params, g)
    return params, l


def prewindows(spec: ModelSpec) -> bool:
    """Whether ``minibatches`` windows the whole series once per round.

    A narrow window is pre-windowed: the series becomes one (n_win, L + H)
    tensor, ``L + H`` times the series and built by that many shifted
    slices, and each step row-gathers its B rows.  A TPU gathers whole rows
    natively but lowers a gather of unaligned slices of the series to a
    serial loop, one iteration per slice: on a TPU v5e, at the paper's
    width (12) over B = 64 and a 256-client cohort, that loop made the
    round 9.4 s against 1.4, while the windowed tensor is a few MB per
    client.  A wide window turns that round: at 2,052 readings the tensor
    is 200 MB per client and its build 2,052 unrolled slices, while a step
    reads B windows, a loop of B slices.  So each step slices its B windows
    out of the series directly.
    """
    return spec.lookback + spec.horizon <= PREWINDOW_MAX_WIDTH


def minibatches(x, y, spec: ModelSpec) -> Callable:
    """``take(idx)``: the windows ``idx`` (B,) of one client's data as the
    spec's batch (``ModelSpec.batch``), from either form of the data.

    Window form: x (n_win, L, 1) and y (n_win, H), row-gathered.  Series
    form (``y is None``): x is the client's (T,) normalized series, window
    k its ``L + H`` values from k, either pre-windowed once per round,
    outside the step loop, or sliced per step (:func:`prewindows`).
    """
    if y is not None:
        return lambda idx: {"x": x[idx], "y": y[idx]}
    width = spec.lookback + spec.horizon
    if not prewindows(spec):
        def take_slices(idx):
            return spec.batch(jax.vmap(
                lambda i: jax.lax.dynamic_slice(x, (i,), (width,)))(idx))
        return take_slices
    n = x.shape[0] - width + 1
    win = jnp.stack([x[q:q + n] for q in range(width)], axis=-1)
    return lambda idx: spec.batch(win[idx])


@functools.partial(jax.jit, static_argnames=("cfg", "loss"))
def local_update(params, x, y, batch_idx, lr, cfg: ModelSpec,
                 loss: Callable, prox_mu=0.0):
    """Run the client's local schedule.

    params: global model (pytree); cfg: the model's spec; x: (n_win, L, 1)
    and y: (n_win, H), or x: (T,) normalized series and y None (see
    :func:`minibatches`); batch_idx: (steps, B) int32; prox_mu: FedProx
    strength (0 = plain FedAvg).  Returns (local params, mean local loss).
    """
    anchor = params                      # round-start global model (FedProx)
    take = minibatches(x, y, cfg)

    def step(p, idx):
        return sgd_step(p, take(idx), lr, cfg, loss, anchor=anchor,
                        prox_mu=prox_mu)

    params, losses = jax.lax.scan(step, params, batch_idx)
    return params, jnp.mean(losses)
