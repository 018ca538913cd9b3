"""The paper's demand forecasters (§3.2): stacked LSTM / GRU + linear head.

Univariate input: a look-back window of L normalized kWh readings, shape
(B, L, input_dim); output: (B, horizon) — multi-step direct forecast, matching
the paper's 8-step look-back / 4-step (1 h) horizon.

Each layer is a ``lax.scan`` of its jnp cell over the look-back, with one
exception: training a one-layer LSTM on a TPU differentiates its whole
look-back through the fused sequence kernels (``kernels/lstm_seq.py``):
forward and backward keep the gates, h and c in VMEM.
:func:`fused_recurrence` decides it from the spec and the platform; the
value the forward returns is the scan's either way.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ForecasterConfig
from repro.models.layers import dense_init


# ------------------------------------------------------------------ init
def init_forecaster(key, cfg: ForecasterConfig, dtype=jnp.float32) -> Dict:
    gates = 4 if cfg.cell == "lstm" else 3
    layers = []
    for l in range(cfg.n_layers):
        inp = cfg.input_dim if l == 0 else cfg.hidden_dim
        key, k1, k2 = jax.random.split(key, 3)
        layers.append({
            "wx": dense_init(k1, inp, gates * cfg.hidden_dim, dtype=dtype),
            "wh": dense_init(k2, cfg.hidden_dim, gates * cfg.hidden_dim,
                             scale=cfg.hidden_dim ** -0.5, dtype=dtype),
            "b": jnp.zeros((gates * cfg.hidden_dim,), dtype),
        })
    key, kh = jax.random.split(key)
    head = {"w": dense_init(kh, cfg.hidden_dim, cfg.horizon, dtype=dtype),
            "b": jnp.zeros((cfg.horizon,), dtype)}
    return {"layers": layers, "head": head}


def param_template(cfg: ForecasterConfig, dtype=jnp.float32) -> Dict:
    """Zero-valued tree with :func:`init_forecaster`'s exact structure.

    The shape/treedef oracle for structure-driven loads (e.g.
    ``checkpoint.unflatten_like`` in the serving registry) — no PRNG key
    needed, since only the skeleton matters.
    """
    gates = 4 if cfg.cell == "lstm" else 3
    layers = []
    for l in range(cfg.n_layers):
        inp = cfg.input_dim if l == 0 else cfg.hidden_dim
        layers.append({
            "wx": jnp.zeros((inp, gates * cfg.hidden_dim), dtype),
            "wh": jnp.zeros((cfg.hidden_dim, gates * cfg.hidden_dim), dtype),
            "b": jnp.zeros((gates * cfg.hidden_dim,), dtype),
        })
    head = {"w": jnp.zeros((cfg.hidden_dim, cfg.horizon), dtype),
            "b": jnp.zeros((cfg.horizon,), dtype)}
    return {"layers": layers, "head": head}


# ------------------------------------------------------------------ cells
def lstm_cell(x_t, h, c, p):
    """One LSTM step (paper §3.2.1). x_t: (B, in); h, c: (B, H)."""
    z = x_t @ p["wx"] + h @ p["wh"] + p["b"]
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
    g = jnp.tanh(g)
    c = f * c + i * g
    h = o * jnp.tanh(c)
    return h, c


def gru_cell(x_t, h, p):
    """One GRU step (paper §3.2.2). Gate layout: [z | r | h̃]."""
    H = h.shape[-1]
    zx = x_t @ p["wx"] + p["b"]
    zh = h @ p["wh"]
    z = jax.nn.sigmoid(zx[..., :H] + zh[..., :H])
    r = jax.nn.sigmoid(zx[..., H:2 * H] + zh[..., H:2 * H])
    h_tilde = jnp.tanh(zx[..., 2 * H:] + r * zh[..., 2 * H:])
    return z * h + (1.0 - z) * h_tilde


# ------------------------------------------------------------------ forward
def fused_recurrence(cfg) -> bool:
    """Whether the LSTM layer is differentiated by the fused sequence
    kernels: on a TPU, for a one-layer LSTM forecaster, at widths the
    kernels' layout holds (``lstm_seq.fits``).  Every other case (the GRU,
    deeper stacks, another model spec, another backend) runs the scan.
    Read at trace time, once per compiled round."""
    from repro.kernels import lstm_seq, platform
    return (isinstance(cfg, ForecasterConfig) and cfg.cell == "lstm"
            and cfg.n_layers == 1 and platform.on_tpu()
            and lstm_seq.fits(cfg.hidden_dim, cfg.lookback, cfg.input_dim))


def _layer(h_seq, p, cell: str):
    """One recurrent layer over the sequence: (B, L, in) -> (B, L, H)."""
    B = h_seq.shape[0]
    H = p["wh"].shape[0]
    dtype = h_seq.dtype
    if cell == "lstm":
        def step(carry, x_t):
            h, c = carry
            h, c = lstm_cell(x_t, h, c, p)
            return (h, c), h
        init = (jnp.zeros((B, H), dtype), jnp.zeros((B, H), dtype))
    else:
        def step(carry, x_t):
            h = gru_cell(x_t, carry[0], p)
            return (h, carry[1]), h
        init = (jnp.zeros((B, H), dtype), jnp.zeros((B, 0), dtype))
    (_, _), hs = jax.lax.scan(step, init, h_seq.swapaxes(0, 1))
    return hs.swapaxes(0, 1)


@jax.custom_vjp
def _lstm_last_h(x, wx, wh, b):
    """The LSTM layer's last hidden state: the scan forward, the fused
    kernels under differentiation."""
    p = {"wx": wx, "wh": wh, "b": b}
    return _layer(x, p, "lstm")[:, -1]


def _lstm_last_h_fwd(x, wx, wh, b):
    from repro.kernels import ops as kops
    h = kops.lstm_seq_forward(x, wx, wh, b)
    return h.astype(x.dtype), (x, wx, wh, b)


def _lstm_last_h_bwd(res, dh):
    from repro.kernels import ops as kops
    grads = kops.lstm_seq_backward(*res, dh)
    return tuple(g.astype(r.dtype) for g, r in zip(grads, res))


_lstm_last_h.defvjp(_lstm_last_h_fwd, _lstm_last_h_bwd)


@functools.partial(jax.jit, static_argnames=("cfg",))
def forecast(params, x, cfg: ForecasterConfig):
    """x: (B, L, input_dim) -> (B, horizon)."""
    if fused_recurrence(cfg):
        p = params["layers"][0]
        h_last = _lstm_last_h(x, p["wx"], p["wh"], p["b"])
    else:
        h_seq = x
        for p in params["layers"]:
            h_seq = _layer(h_seq, p, cfg.cell)
        h_last = h_seq[:, -1]
    return h_last @ params["head"]["w"] + params["head"]["b"]


def loss_fn(params, batch, cfg: ForecasterConfig, loss):
    """batch: {"x": (B,L,1), "y": (B,horizon)} -> scalar loss."""
    pred = forecast(params, batch["x"], cfg)
    return loss(pred, batch["y"])
