"""Plain reference of the paper's recurrent demand forecasters (§3.2).

Written from the paper and the configuration file alone, in straightforward
``jax.numpy``: one LSTM or GRU layer over a look-back window of min-max
normalised readings, a linear head to the horizon, the exponentially
weighted MSE (§3.3.2), minibatch SGD on each client and the uniform FedAvg
average (Alg. 1).  It imports nothing of the system under test.

``dtype`` is the precision of every array and operation.  float32 runs its
matmuls at ``Precision.HIGHEST`` (a TPU would otherwise round their inputs
to bfloat16); bfloat16 is the control, the precision below the one the
configurations state.

Gate layouts follow the configuration's ``gate_order``: LSTM ``[i|f|g|o]``,
GRU ``[z|r|n]`` with the reset gate applied to the hidden projection
(``n = tanh(W_x x + b + r * (W_h h))``) and ``h' = z * h + (1 - z) * n``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GATES = {"lstm": 4, "gru": 3}


def _hp(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _mm(a, b, dtype):
    return jnp.matmul(a, b, precision=_hp(dtype),
                      preferred_element_type=dtype)


# ------------------------------------------------------------------ init
def init_params(seed: int, cfg: dict, stream: int = 0):
    """Initial weights from a seed, as the configuration's ``init`` states:
    key ``fold_in(PRNGKey(seed), stream)``; per layer two splits for the
    input and hidden matrices (normal, scaled by fan-in ** -0.5), zero bias;
    one split for the head (normal, hidden ** -0.5), zero bias."""
    return _init(jax.random.PRNGKey(seed), cfg, stream)


@functools.partial(jax.jit, static_argnames=("cfg", "streams"))
def make_weights(key, cfg: tuple, streams: tuple):
    """One set of initial weights per stream, in one call on the device."""
    return [_init(key, dict(cfg), s) for s in streams]


def _init(key, cfg: dict, stream: int):
    H, G = cfg["hidden_dim"], GATES[cfg["cell"]] * cfg["hidden_dim"]
    key = jax.random.fold_in(key, stream)
    layers = []
    for l in range(cfg["n_layers"]):
        inp = cfg["input_dim"] if l == 0 else H
        key, k1, k2 = jax.random.split(key, 3)
        layers.append({
            "wx": jax.random.normal(k1, (inp, G), jnp.float32) * inp ** -0.5,
            "wh": jax.random.normal(k2, (H, G), jnp.float32) * H ** -0.5,
            "b": jnp.zeros((G,), jnp.float32)})
    key, kh = jax.random.split(key)
    head = {"w": jax.random.normal(kh, (H, cfg["horizon"]),
                                   jnp.float32) * H ** -0.5,
            "b": jnp.zeros((cfg["horizon"],), jnp.float32)}
    return {"layers": layers, "head": head}


# --------------------------------------------------------------- forward
def _lstm_step(p, carry, x_t, dtype):
    h, c = carry
    z = _mm(x_t, p["wx"], dtype) + _mm(h, p["wh"], dtype) + p["b"]
    H = h.shape[-1]
    i = jax.nn.sigmoid(z[:, :H])
    f = jax.nn.sigmoid(z[:, H:2 * H])
    g = jnp.tanh(z[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(z[:, 3 * H:])
    c = f * c + i * g
    return (o * jnp.tanh(c), c)


def _gru_step(p, carry, x_t, dtype):
    h, c = carry
    H = h.shape[-1]
    zx = _mm(x_t, p["wx"], dtype) + p["b"]
    zh = _mm(h, p["wh"], dtype)
    z = jax.nn.sigmoid(zx[:, :H] + zh[:, :H])
    r = jax.nn.sigmoid(zx[:, H:2 * H] + zh[:, H:2 * H])
    n = jnp.tanh(zx[:, 2 * H:] + r * zh[:, 2 * H:])
    return (z * h + (1 - z) * n, c)


def forward(params, x, cfg: dict, dtype=jnp.float32):
    """x: (B, L, input_dim) normalised -> (B, horizon)."""
    step = _lstm_step if cfg["cell"] == "lstm" else _gru_step
    B, H = x.shape[0], cfg["hidden_dim"]
    seq = x.astype(dtype)
    for p in params["layers"]:
        carry = (jnp.zeros((B, H), dtype), jnp.zeros((B, H), dtype))
        outs = []
        for t in range(seq.shape[1]):
            carry = step(p, carry, seq[:, t], dtype)
            outs.append(carry[0])
        seq = jnp.stack(outs, axis=1)
    return _mm(seq[:, -1], params["head"]["w"], dtype) + params["head"]["b"]


def ew_mse(pred, y, beta: float):
    """(1/N) sum_i beta^(i-1) (y_i - pred_i)^2 over rows and horizon."""
    w = jnp.asarray(beta, pred.dtype) ** jnp.arange(pred.shape[-1],
                                                    dtype=pred.dtype)
    d = pred - y.astype(pred.dtype)
    return jnp.mean(d * d * w)


def cast(params, dtype):
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), params)


# --------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _serve_block(params, x, lo, hi, cfg, dtype):
    x, lo, hi = x.astype(dtype), lo.astype(dtype), hi.astype(dtype)
    scale = jnp.maximum(hi - lo, jnp.asarray(1e-9, dtype))
    pred = forward(cast(params, dtype), ((x - lo) / scale)[..., None],
                   dict(cfg), dtype)
    return (pred * scale + lo).astype(jnp.float32)


def serve(params, x, lo, hi, cfg: dict, dtype=jnp.float32,
          block: int = 65536) -> np.ndarray:
    """Raw readings (n, L) and each row's (lo, hi) -> (n, horizon) kWh.
    Rows run in blocks of ``block`` (the last padded), so it fits."""
    key = tuple(sorted(cfg.items()))
    n = x.shape[0]
    out = []
    for s in range(0, n, block):
        xb, lb, hb = x[s:s + block], lo[s:s + block], hi[s:s + block]
        k = xb.shape[0]
        if k < block:
            pad = ((0, block - k), (0, 0))
            xb, lb = np.pad(xb, pad), np.pad(lb, pad)
            hb = np.pad(hb, pad, constant_values=1.0)
        out.append(np.asarray(_serve_block(params, xb, lb, hb, key,
                                           dtype))[:k])
    return np.concatenate(out)


# -------------------------------------------------------------- training
def normalise(series, dtype=jnp.float32):
    """Min-max over each client's whole series: (m, T) -> (m, T)."""
    s = jnp.asarray(series).astype(dtype)
    lo = s.min(axis=1, keepdims=True)
    hi = s.max(axis=1, keepdims=True)
    return (s - lo) / jnp.maximum(hi - lo, jnp.asarray(1e-9, dtype))


def _client_update(params, norm, bidx, lr, cfg, beta, dtype):
    """Minibatch SGD of one client.  ``norm``: (cut,) normalised training
    part; window j is ``norm[j:j+L]`` -> ``norm[j+L:j+L+horizon]``;
    ``bidx``: (steps, B) window ids.  Returns (params, mean step loss)."""
    L, Hz = cfg["lookback"], cfg["horizon"]

    def loss(p, idx):
        x = norm[idx[:, None] + jnp.arange(L)][..., None]
        y = norm[idx[:, None] + L + jnp.arange(Hz)]
        return ew_mse(forward(p, x, cfg, dtype), y, beta)

    def step(p, idx):
        l, g = jax.value_and_grad(loss)(p, idx)
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g), l

    p, ls = jax.lax.scan(step, params, bidx)
    return p, jnp.mean(ls.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("cfg", "beta", "lr", "dtype",
                                             "keep"))
def _round(params, norm, bidx, cfg, beta, lr, dtype, keep):
    """One FedAvg round over the clients ``norm`` (m, cut): each client's
    SGD from the same global model, then the uniform mean of the first
    ``keep`` clients' models and losses (``keep = m`` for the round as the
    algorithm states it)."""
    cfgd = dict(cfg)
    lr = jnp.asarray(lr, dtype)
    locals_, losses = jax.vmap(
        lambda n, b: _client_update(params, n, b, lr, cfgd, beta, dtype))(
        norm, bidx)
    avg = jax.tree.map(lambda w: jnp.mean(w[:keep], axis=0), locals_)
    return avg, jnp.mean(losses[:keep])


def fedavg_rounds(series: np.ndarray, seed: int, cfg: dict, fl: dict,
                  rounds: int, dtype=jnp.float32, fault: str = ""):
    """``rounds`` rounds of federated SGD from the seed's initial weights.

    ``series``: (N, T) raw readings of the fleet.  ``fl`` gives
    ``clients_per_round``, ``batch_size``, ``lr``, ``beta``, ``train_frac``.
    Selection as the algorithm's driver states it: a numpy generator from
    the second child of ``SeedSequence(seed)``; each round draws the cohort
    without replacement, then the (m, steps, B) window ids.

    ``fault`` plants a known error, for reading what the comparison sees:
    ``half_batch`` trains each step on half of its rows; ``no_exchange``
    averages only the first quarter of the cohort (one chip of four).

    Returns the parameters after each round and the round losses.
    """
    N, T = series.shape
    m, B = fl["clients_per_round"], fl["batch_size"]
    L, Hz = cfg["lookback"], cfg["horizon"]
    cut = int(T * fl["train_frac"])
    n_win = cut - L - Hz + 1
    steps = -(-n_win // B)
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(2)[1])
    params = cast(init_params(seed, cfg), dtype)
    keep = m // 4 if fault == "no_exchange" else m
    key = tuple(sorted(cfg.items()))
    out, losses = [], []
    with jax.default_matmul_precision("highest" if dtype == jnp.float32
                                      else "default"):
        for _ in range(rounds):
            sel = rng.choice(N, size=m, replace=False)
            bidx = rng.integers(0, n_win, size=(m, steps, B))
            if fault == "half_batch":
                bidx = bidx[:, :, :B // 2]
            norm = normalise(series[sel], dtype)[:, :cut]
            params, loss = _round(params, norm, jnp.asarray(bidx, jnp.int32),
                                  key, fl["beta"], fl["lr"], dtype, keep)
            out.append(jax.tree.map(lambda a: np.asarray(a, np.float64),
                                    params))
            losses.append(float(loss))
    return out, losses
