"""Plain reference of the hybrid Mamba2/attention load forecaster.

Written from the configuration (``HybridForecasterConfig``'s fields as a dict)
and granite-4.0-h's published block alone, in straightforward
``jax.numpy``; it imports nothing of the system under test.  Each 15-minute
reading is one position; every position forecasts the next ``horizon``
readings.  One layer::

    h   = x + r * mixer(RMSNorm(x))
    out = h + r * (SiLU(x W_gate) * (x W_in)) W_out     on RMSNorm(h)

Mamba2 mixer: ``in_proj`` -> [z | xBC | dt]; causal depthwise conv (with
bias) over xBC, then SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log);
the state recurrence, one step at a time,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,     y_t = h_t C_t + D x_t,

then RMSNorm(y * SiLU(z)) and ``out_proj``.  Attention mixer: causal GQA
with no position embedding (NoPE), scores scaled by
``attention_multiplier``.  The input is a linear value embedding times
``embedding_multiplier``; a final RMSNorm and a linear head, divided by
``logits_scaling``, give the forecasts.  The loss is the exponentially
weighted MSE (β^(i-1) at horizon step i) over every position.

Departures from the published granite-4.0-h-micro, as the configuration
states them: 10 of its 40 layers (its layers 10-19); a linear value
embedding (1 -> d) in place of the token embedding and a linear head
(d -> horizon) in place of the tied LM head, so the vocabulary is unused;
the MLP's fused ``input_linear`` kept as two matrices (gate, up), which
is the same map; initial weights from a seed, as ``init_params`` states.

``dtype`` is the precision of every array and operation.  float32 runs its
matmuls at ``Precision.HIGHEST`` (a TPU would otherwise round their inputs
to bfloat16); bfloat16 is the control, the precision below the one the
configuration states.  To fit one chip at full size, each layer, each
``block`` steps of the recurrence and each ``q_block`` query rows of
attention are rematerialised, and the clients of a round run one after
another.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _hp(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _ein(spec, a, b, dtype):
    return jnp.einsum(spec, a, b, precision=_hp(dtype),
                      preferred_element_type=dtype)


def _dims(cfg):
    s = cfg["ssm"]
    d_in = s["expand"] * cfg["d_model"]
    return s, d_in, d_in // s["head_dim"], s["n_groups"] * s["state_dim"]


# ------------------------------------------------------------------ init
def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _init_mamba(key, cfg):
    s, d_in, nh, gdim = _dims(cfg)
    d = cfg["d_model"]
    ks = jax.random.split(key, 5)
    a = jax.random.uniform(ks[2], (nh,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[3], (nh,), jnp.float32,
                                    math.log(1e-3), math.log(0.1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "in_proj": _normal(ks[0], (d, 2 * d_in + 2 * gdim + nh), d ** -0.5),
        "conv_w": _normal(ks[1], (s["conv_width"], d_in + 2 * gdim), 0.2),
        "conv_b": jnp.zeros((d_in + 2 * gdim,), jnp.float32),
        "a_log": jnp.log(a),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm_w": jnp.ones((d_in,), jnp.float32),
        "out_proj": _normal(ks[4], (d_in, d), d_in ** -0.5),
    }


def _init_attention(key, cfg):
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    ks = jax.random.split(key, 4)
    return {"wq": _normal(ks[0], (d, H * hd), d ** -0.5),
            "wk": _normal(ks[1], (d, Hkv * hd), d ** -0.5),
            "wv": _normal(ks[2], (d, Hkv * hd), d ** -0.5),
            "wo": _normal(ks[3], (H * hd, d), (H * hd) ** -0.5)}


def _init_mlp(key, cfg):
    d, f = cfg["d_model"], cfg["d_ff"]
    ks = jax.random.split(key, 3)
    return {"w_in": _normal(ks[0], (d, f), d ** -0.5),
            "w_gate": _normal(ks[1], (d, f), d ** -0.5),
            "w_out": _normal(ks[2], (f, d), f ** -0.5)}


@functools.partial(jax.jit, static_argnames=("cfg", "stream"))
def _init(seed_key, cfg: tuple, stream: int):
    cfg = _thaw(cfg)
    d, n = cfg["d_model"], len(cfg["layer_types"])
    key = jax.random.fold_in(seed_key, stream)
    layers = []
    for i, kind in enumerate(cfg["layer_types"]):
        k_mix, k_mlp = jax.random.split(jax.random.fold_in(key, i))
        mixer = (_init_mamba(k_mix, cfg) if kind == "mamba"
                 else _init_attention(k_mix, cfg))
        layers.append({"norm1": jnp.ones((d,), jnp.float32), "mixer": mixer,
                       "norm2": jnp.ones((d,), jnp.float32),
                       "mlp": _init_mlp(k_mlp, cfg)})
    ke_w, ke_b = jax.random.split(jax.random.fold_in(key, n))
    kh = jax.random.fold_in(key, n + 1)
    return {"embed": {"w": _normal(ke_w, (1, d), 1.0),
                      "b": _normal(ke_b, (d,), 1.0)},
            "layers": layers, "norm": jnp.ones((d,), jnp.float32),
            "head": {"w": _normal(kh, (d, cfg["horizon"]), d ** -0.5),
                     "b": jnp.zeros((cfg["horizon"],), jnp.float32)}}


def init_params(seed: int, cfg: dict, stream: int = 0):
    """Initial weights from a seed, as the configuration's ``init`` states:
    key ``fold_in(PRNGKey(seed), stream)``; layer i draws from
    ``fold_in(key, i)``, split into a mixer and an MLP key.  Mamba2: five
    splits (in_proj normal d^-0.5; conv normal 0.2, zero bias; A uniform
    in [1, 16], A_log = log A; dt log-uniform in [1e-3, 0.1], floored at
    1e-4, dt_bias its inverse softplus; out_proj normal d_in^-0.5), D and
    the norm one.  Attention: four splits (q, k, v normal d^-0.5; o normal
    (H hd)^-0.5).  MLP: three splits (up, gate normal d^-0.5; down normal
    d_ff^-0.5).  Every RMSNorm weight one.  Embedding weight and bias
    normal scale 1 from the two splits of ``fold_in(key, n_layers)``; head
    normal d^-0.5 from ``fold_in(key, n_layers + 1)``, zero bias."""
    return _init(jax.random.PRNGKey(seed), _freeze(cfg), stream)


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()))


def _thaw(cfg: tuple) -> dict:
    out = dict(cfg)
    out["ssm"] = dict(out["ssm"])
    return out


# --------------------------------------------------------------- forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _mamba(p, u, cfg, dtype, block):
    s, d_in, nh, gdim = _dims(cfg)
    B, S, _ = u.shape
    hd, N, K = s["head_dim"], s["state_dim"], s["conv_width"]
    proj = _ein("bsd,dk->bsk", u, p["in_proj"], dtype)
    z, xbc, dt = (proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * gdim],
                  proj[..., 2 * d_in + 2 * gdim:])
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + S] * p["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[..., :d_in].reshape(B, S, nh, hd)
    rep = nh // s["n_groups"]
    Bm = jnp.repeat(xbc[..., d_in:d_in + gdim].reshape(
        B, S, s["n_groups"], N), rep, axis=2)                 # (B,S,nh,N)
    Cm = jnp.repeat(xbc[..., d_in + gdim:].reshape(
        B, S, s["n_groups"], N), rep, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # (B,S,nh)
    A = -jnp.exp(p["a_log"])

    # the recurrence, one step at a time; steps past S (padding to whole
    # blocks) have dt = 0: decay 1, no contribution
    pad = (-S) % block
    tm = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)
                           ).swapaxes(0, 1)
    xs_t, B_t, C_t, dt_t = tm(x), tm(Bm), tm(Cm), tm(dt)
    nb = (S + pad) // block
    blk = lambda t: t.reshape(nb, block, *t.shape[1:])

    def step(h, inp):
        x_t, b_t, c_t, d_t = inp
        h = (jnp.exp(d_t * A)[..., None, None] * h
             + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, _ein("bhpn,bhn->bhp", h, c_t, dtype)

    @jax.checkpoint
    def run_block(h, inp):
        return jax.lax.scan(step, h, inp, unroll=8)

    h0 = jnp.zeros((B, nh, hd, N), dtype)
    _, ys = jax.lax.scan(run_block, h0, tuple(map(blk, (xs_t, B_t, C_t,
                                                        dt_t))))
    y = ys.reshape(nb * block, B, nh, hd).swapaxes(0, 1)[:, :S]
    y = (y + x * p["d_skip"][:, None]).reshape(B, S, d_in)
    y = _rms(y * jax.nn.silu(z), p["norm_w"], cfg["norm_eps"])
    return _ein("bsk,kd->bsd", y, p["out_proj"], dtype)


def _attention(p, u, cfg, dtype, q_block):
    B, S, _ = u.shape
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = _ein("bsd,dk->bsk", u, p["wq"], dtype).reshape(B, S, H, hd)
    k = _ein("bsd,dk->bsk", u, p["wk"], dtype).reshape(B, S, Hkv, hd)
    v = _ein("bsd,dk->bsk", u, p["wv"], dtype).reshape(B, S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=2)                       # head j: kv j//G
    v = jnp.repeat(v, H // Hkv, axis=2)

    @jax.checkpoint
    def rows(q_rows, start):
        s = _ein("bqhd,bkhd->bhqk", q_rows, k, dtype) * cfg[
            "attention_multiplier"]
        i = start + jnp.arange(q_rows.shape[1])[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= i, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return _ein("bhqk,bkhd->bqhd", w, v, dtype)

    outs = [rows(q[:, a:a + q_block], a) for a in range(0, S, q_block)]
    o = jnp.concatenate(outs, axis=1).reshape(B, S, H * hd)
    return _ein("bsk,kd->bsd", o, p["wo"], dtype)


def _mlp(p, u, dtype):
    g = _ein("bsd,df->bsf", u, p["w_gate"], dtype)
    up = _ein("bsd,df->bsf", u, p["w_in"], dtype)
    return _ein("bsf,fd->bsd", jax.nn.silu(g) * up, p["w_out"], dtype)


def forward(params, x, cfg: dict, dtype=jnp.float32, block: int = 64,
            q_block: int = 256):
    """x: (B, L) normalised readings -> (B, L, horizon)."""
    eps, r = cfg["norm_eps"], cfg["residual_multiplier"]
    e = params["embed"]
    h = (x.astype(dtype)[..., None] * e["w"][0] + e["b"]) \
        * cfg["embedding_multiplier"]
    for kind, p in zip(cfg["layer_types"], params["layers"]):
        def layer(h, p, kind=kind):
            u = _rms(h, p["norm1"], eps)
            mix = (_mamba(p["mixer"], u, cfg, dtype, block) if kind == "mamba"
                   else _attention(p["mixer"], u, cfg, dtype, q_block))
            h = h + r * mix
            return h + r * _mlp(p["mlp"], _rms(h, p["norm2"], eps), dtype)
        h = jax.checkpoint(layer)(h, p)
    h = _rms(h, params["norm"], eps)
    return (_ein("bsd,dk->bsk", h, params["head"]["w"], dtype)
            + params["head"]["b"]) / cfg["logits_scaling"]


def ew_mse(pred, y, beta: float):
    """(1/N) sum beta^(i-1) (y_i - pred_i)^2 over every element."""
    w = jnp.asarray(beta, pred.dtype) ** jnp.arange(pred.shape[-1],
                                                    dtype=pred.dtype)
    d = pred - y.astype(pred.dtype)
    return jnp.mean(d * d * w)


def targets(windows, L: int, horizon: int):
    """(B, L + horizon) windows -> inputs (B, L) and, at each position t,
    the ``horizon`` readings after it (B, L, horizon)."""
    return windows[:, :L], jnp.stack(
        [windows[:, 1 + h:1 + h + L] for h in range(horizon)], axis=-1)


def loss(params, windows, cfg: dict, beta: float, dtype=jnp.float32,
         **blocks):
    x, y = targets(windows, cfg["lookback"], cfg["horizon"])
    return ew_mse(forward(params, x, cfg, dtype, **blocks), y, beta)


def cast(params, dtype):
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), params)


# -------------------------------------------------------------- training
def normalise(series, dtype=jnp.float32):
    """Min-max over each client's whole series: (m, T) -> (m, T)."""
    s = jnp.asarray(series).astype(dtype)
    lo = s.min(axis=1, keepdims=True)
    hi = s.max(axis=1, keepdims=True)
    return (s - lo) / jnp.maximum(hi - lo, jnp.asarray(1e-9, dtype))


@functools.partial(jax.jit, static_argnames=("cfg", "beta", "dtype", "block",
                                             "q_block"), donate_argnums=(0,))
def _sgd_step(params, windows, lr, cfg, beta, dtype, block, q_block):
    l, g = jax.value_and_grad(loss)(params, windows, _thaw(cfg), beta, dtype,
                                    block=block, q_block=q_block)
    return jax.tree.map(lambda w, gw: w - lr * gw, params, g), l


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, local):
    return jax.tree.map(jnp.add, acc, local)


@functools.partial(jax.jit, static_argnames=("m",), donate_argnums=(0,))
def _mean(acc, m):
    return jax.tree.map(lambda a: a / m, acc)


def _names(tree) -> dict:
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp): a
            for kp, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def _norms(before, after):
    return jax.tree.map(lambda b, a: jnp.linalg.norm(
        (a.astype(jnp.float32) - b.astype(jnp.float32)).reshape(-1)),
        before, after)


def change_norms(before, after) -> dict:
    """L2 norm of each weight's change, by name (``layers/<i>/...``)."""
    return {k: float(v) for k, v in _names(_norms(before, after)).items()}


def fedavg_rounds(series: np.ndarray, seed: int, cfg: dict, fl: dict,
                  rounds: int, dtype=jnp.float32, fault: str = "",
                  block: int = 64, q_block: int = 256):
    """``rounds`` rounds of cross-silo FedAvg from the seed's weights.

    ``series``: (N, T) raw readings.  ``fl`` gives ``clients_per_round``,
    ``local_steps`` (K), ``batch_size`` (B), ``lr``, ``beta`` and
    ``train_frac``.  Selection as the host round loop states it: a numpy
    generator from the second child of ``SeedSequence(seed)``; each round
    draws the cohort without replacement, then the (m, K, B) window
    starts, uniformly over each client's windows.  Each client runs K SGD
    steps from the global model; the new global model is the clients'
    uniform mean.

    ``fault`` plants a known error: ``half_batch`` trains each step on half
    of its windows.

    Returns the change norms from the initial weights after each round
    (:func:`change_norms`) and the round losses (the mean over clients of
    each client's mean step loss).
    """
    N, T = series.shape
    m, K, B = fl["clients_per_round"], fl["local_steps"], fl["batch_size"]
    L, Hz = cfg["lookback"], cfg["horizon"]
    cut = int(T * fl["train_frac"])
    n_win = cut - L - Hz + 1
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(2)[1])
    frozen = _freeze(cfg)
    lr = jnp.asarray(fl["lr"], dtype)
    params = cast(init_params(seed, cfg), dtype)
    norms, losses = [], []
    with jax.default_matmul_precision("highest" if dtype == jnp.float32
                                      else "default"):
        for _ in range(rounds):
            sel = rng.choice(N, size=m, replace=False)
            starts = rng.integers(0, n_win, size=(m, K, B))
            if fault == "half_batch":
                starts = starts[:, :, :B // 2]
            norm = np.asarray(normalise(series[sel], dtype)[:, :cut])
            acc, client_losses = None, []
            for i in range(m):
                local = jax.tree.map(jnp.copy, params)
                step_losses = []
                for k in range(K):
                    win = norm[i][starts[i, k][:, None]
                                  + np.arange(L + Hz)]
                    local, l = _sgd_step(local, jnp.asarray(win), lr, frozen,
                                         fl["beta"], dtype, block, q_block)
                    step_losses.append(float(l))
                client_losses.append(np.mean(step_losses))
                acc = local if acc is None else _add(acc, local)
                del local
            params = _mean(acc, m)
            del acc
            w0 = cast(init_params(seed, cfg), dtype)
            norms.append(change_norms(w0, params))
            del w0
            losses.append(float(np.mean(client_losses)))
    return norms, losses
