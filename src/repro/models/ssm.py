"""Mamba2 (SSD — state-space duality) block, chunked-scan formulation.

Training/prefill uses the chunkwise algorithm (Dao & Gu 2024): within a chunk
of Q tokens the output is a masked quadratic form (MXU-friendly); across
chunks a single ``lax.scan`` carries the (nh, hd, ds) state.  Decode is the
plain single-step recurrence against a conv ring buffer + SSM state.

Layout: x (B, S, d) → in_proj → [z | xBC | dt]; depthwise causal conv over
xBC; heads nh = d_inner / head_dim; per-head scalar decay a_t = exp(A · dt_t)
with A = -exp(A_log) and dt_t = softplus(dt_t + dt_bias) (Mamba2's
scalar-identity A).  Gated RMSNorm before out_proj.

Initialisation follows the published Mamba2: A uniform in [1, 16] (stored
as A_log), dt log-uniform in [1e-3, 0.1] (floored at 1e-4) and stored as
the inverse softplus in ``dt_bias``, D = 1.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.models.layers import dense_init, rms_norm
from repro.sharding import constrain

A_INIT = (1.0, 16.0)          # A = -exp(a_log), uniform in this range
DT_INIT = (1e-3, 0.1)         # dt log-uniform in this range ...
DT_FLOOR = 1e-4               # ... and at least this


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    return s, d_in, nh


def init_ssm(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    s, d_in, nh = _dims(cfg)
    d = cfg.d_model
    conv_dim = d_in + 2 * s.n_groups * s.state_dim
    ks = jax.random.split(key, 5)
    a = jax.random.uniform(ks[2], (nh,), jnp.float32, A_INIT[0], A_INIT[1])
    dt = jnp.exp(jax.random.uniform(ks[3], (nh,), jnp.float32,
                                    math.log(DT_INIT[0]),
                                    math.log(DT_INIT[1])))
    dt = jnp.maximum(dt, DT_FLOOR)
    return {
        "in_proj": dense_init(ks[0], d, 2 * d_in + 2 * s.n_groups * s.state_dim
                              + nh, dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, conv_dim),
                                     jnp.float32) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(a),                             # A = -exp(a_log)
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),        # softplus^-1(dt)
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm_w": jnp.ones((d_in,), dtype),
        "out_proj": dense_init(ks[4], d_in, d, scale=d_in ** -0.5, dtype=dtype),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    s, d_in, nh = _dims(cfg)
    gdim = s.n_groups * s.state_dim
    z = zxbcdt[..., :d_in]
    xBC = zxbcdt[..., d_in:2 * d_in + 2 * gdim]
    dt = zxbcdt[..., 2 * d_in + 2 * gdim:]
    return z, xBC, dt


def _conv(xBC, w, b):
    """Depthwise causal conv over sequence. xBC: (B,S,Cd); w: (K,Cd)."""
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1]] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def _conv_step(x_t, conv_state, w, b):
    """x_t: (B,Cd); conv_state: (B,K-1,Cd) most-recent-last."""
    window = jnp.concatenate([conv_state, x_t[:, None]], axis=1)   # (B,K,Cd)
    out = jnp.einsum("bkc,kc->bc", window, w) + b
    return jax.nn.silu(out), window[:, 1:]


def _heads(xBC, dt, params, cfg: ModelConfig):
    s, d_in, nh = _dims(cfg)
    gdim = s.n_groups * s.state_dim
    x = xBC[..., :d_in]
    Bm = xBC[..., d_in:d_in + gdim]
    Cm = xBC[..., d_in + gdim:]
    shp = x.shape[:-1]
    x = x.reshape(*shp, nh, s.head_dim)
    Bm = Bm.reshape(*shp, s.n_groups, s.state_dim)
    Cm = Cm.reshape(*shp, s.n_groups, s.state_dim)
    # broadcast groups over heads
    rep = nh // s.n_groups
    Bm = jnp.repeat(Bm, rep, axis=-2)
    Cm = jnp.repeat(Cm, rep, axis=-2)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (...,nh)
    a = -jnp.exp(params["a_log"])                        # (nh,) negative decay
    return x, Bm, Cm, dt, a * dt                         # log of the decay


def ssm_forward(params, x, cfg: ModelConfig, *, state=None
                ) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence chunked SSD. x: (B, S, d) -> (B, S, d).

    Returns (out, final_state) — state = {"ssm": (B,nh,hd,ds), "conv": (B,K-1,Cd)}.
    """
    s, d_in, nh = _dims(cfg)
    B, S, _ = x.shape
    Q = min(s.chunk_size, S)
    pad = (-S) % Q
    nc = (S + pad) // Q

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, params["in_proj"].astype(x.dtype))
    z, xBC_raw, dt_raw = _split_proj(zxbcdt, cfg)
    xBC = _conv(xBC_raw, params["conv_w"].astype(x.dtype), params["conv_b"]
                .astype(x.dtype))
    xh, Bm, Cm, dt, logdec = _heads(xBC, dt_raw, params, cfg)
    xh = constrain(xh, "batch", None, "act_heads", None)
    if pad:
        # pad to a chunk multiple with IDENTITY steps: decay=1 (log 0),
        # contribution=0
        pz = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        xh, Bm, Cm, dt, logdec = map(pz, (xh, Bm, Cm, dt, logdec))

    # chunk to (nc, B, Q, ...) and scan over chunks — bounds the quadratic
    # intra-chunk intermediate at one (B, Q, Q, nh) block at a time
    ch = lambda t: t.reshape(B, nc, Q, *t.shape[2:]).swapaxes(0, 1)
    xh_c, Bm_c, Cm_c, dt_c, logdec_c = map(ch, (xh, Bm, Cm, dt, logdec))
    xdt_c = xh_c * dt_c[..., None].astype(xh_c.dtype)    # fold dt into x

    init = (jnp.zeros((B, nh, s.head_dim, s.state_dim), jnp.float32)
            if state is None else state["ssm"])
    iq = jnp.arange(Q)
    causal = iq[:, None] >= iq[None, :]

    # rematerialised: the backward pass keeps the (B, nh, hd, ds) state at
    # each chunk's start and recomputes the chunk's (B, Q, Q, nh) terms,
    # in place of stacking them for every chunk
    @jax.checkpoint
    def scan_body(st, inp):
        xdt, Bc, Cc, logdec = inp                        # (B,Q,...) one chunk
        cum = jnp.cumsum(logdec, axis=1)                 # inclusive, fp32
        seg = cum[:, :, None, :] - cum[:, None, :, :]    # (B,Qi,Qj,nh)
        # mask BEFORE the exp: above the diagonal seg is positive, its exp
        # overflows, and a where after the exp turns the overflow into a
        # NaN gradient
        L = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bqhn,bkhn->bqkh", Cc, Bc)       # (B,Qi,Qj,nh)
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", cb * L.astype(cb.dtype), xdt)
        # inter-chunk: C_t · decay_from_chunk_start · st
        dfs = jnp.exp(cum)                               # (B,Q,nh)
        y_inter = jnp.einsum("bqhn,bhpn->bqhp",
                             Cc * dfs[..., None].astype(Cc.dtype),
                             st.astype(Cc.dtype))
        # state update: st' = decay_whole · st + Σ_j decay_to_end_j · B_j x_j
        dte = jnp.exp(cum[:, -1:, :] - cum)              # (B,Q,nh)
        contrib = jnp.einsum("bqhn,bqhp->bhpn",
                             (Bc * dte[..., None].astype(Bc.dtype))
                             .astype(jnp.float32), xdt.astype(jnp.float32))
        st = st * jnp.exp(cum[:, -1, :])[..., None, None] + contrib
        return st, y_intra + y_inter

    with jax.named_scope("ssd"):
        final_state, y_c = jax.lax.scan(scan_body, init,
                                        (xdt_c, Bm_c, Cm_c, logdec_c))
    y = y_c.swapaxes(0, 1).reshape(B, S + pad, nh, s.head_dim)[:, :S]
    y = y + xh[:, :S] * params["d_skip"][:, None].astype(y.dtype)
    y = y.reshape(B, S, d_in)
    y = rms_norm(y * jax.nn.silu(z), params["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, params["out_proj"].astype(x.dtype))

    new_conv = jnp.swapaxes(xBC_raw[:, S - (s.conv_width - 1):], 0, 0)
    return out, {"ssm": final_state, "conv": new_conv}


def ssm_decode(params, x, state, cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """Single-token recurrence. x: (B, 1, d); state from ``init_ssm_state``."""
    s, d_in, nh = _dims(cfg)
    B = x.shape[0]
    zxbcdt = jnp.einsum("bd,dk->bk", x[:, 0], params["in_proj"].astype(x.dtype))
    z, xBC_raw, dt_raw = _split_proj(zxbcdt, cfg)
    xBC, new_conv = _conv_step(xBC_raw, state["conv"],
                               params["conv_w"].astype(x.dtype),
                               params["conv_b"].astype(x.dtype))
    xh, Bm, Cm, dt, logdec = _heads(xBC, dt_raw, params, cfg)  # (B,nh,hd) etc.
    decay = jnp.exp(logdec)

    st = state["ssm"]                                    # (B,nh,hd,ds) fp32
    contrib = jnp.einsum("bhn,bhp->bhpn", Bm.astype(jnp.float32),
                         (xh * dt[..., None].astype(xh.dtype))
                         .astype(jnp.float32))
    st = st * decay[..., None, None] + contrib
    y = jnp.einsum("bhn,bhpn->bhp", Cm.astype(jnp.float32), st)
    y = y.astype(x.dtype) + xh * params["d_skip"][:, None].astype(x.dtype)
    y = y.reshape(B, d_in)
    y = rms_norm(y * jax.nn.silu(z), params["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bk,kd->bd", y, params["out_proj"].astype(x.dtype))
    return out[:, None], {"ssm": st, "conv": new_conv}


def init_ssm_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> Dict:
    s, d_in, nh = _dims(cfg)
    conv_dim = d_in + 2 * s.n_groups * s.state_dim
    return {
        "ssm": jnp.zeros((batch, nh, s.head_dim, s.state_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
    }
