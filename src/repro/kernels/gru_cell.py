"""Fused GRU cell — Pallas TPU kernel (3 gates, reset-gate ordering).

Same fusion rationale as the LSTM cell; the GRU's reset gate makes the
candidate depend on r ⊙ (h·Wh_h̃), so the kernel computes zx = x·Wx + b and
zh = h·Wh per gate and combines gates in VREGs.  Public weight layout:
(I, 3H) / (H, 3H) / (3H,), gate order [z | r | h̃]; the jitted wrapper
relays it out gate-first — (3, I, H) / (3, H, H) / (3, 1, H) — so the
kernel takes one 2-D ``jnp.dot`` per gate and reshapes nothing across the
tiled dims (see ``lstm_cell.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def _gru_kernel(x_ref, h_ref, hblk_ref, wx_ref, wh_ref, b_ref, h_out_ref):
    x = x_ref[...]                                       # (bt, I)
    h = h_ref[...]                                       # (bt, H) full

    def zx(k):                                           # (bt, ht) f32
        return (jnp.dot(x, wx_ref[k], preferred_element_type=jnp.float32)
                + b_ref[k].astype(jnp.float32))

    def zh(k):
        return jnp.dot(h, wh_ref[k], preferred_element_type=jnp.float32)

    z = jax.nn.sigmoid(zx(0) + zh(0))
    r = jax.nn.sigmoid(zx(1) + zh(1))
    h_tilde = jnp.tanh(zx(2) + r * zh(2))
    out = z * hblk_ref[...].astype(jnp.float32) + (1.0 - z) * h_tilde
    h_out_ref[...] = out.astype(h_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_h", "interpret"))
def gru_cell(x, h, wx, wh, b, *, block_b: int = 128, block_h: int = 128,
             interpret: Optional[bool] = None):
    """Fused GRU step.  x: (B, I); h: (B, H); wx: (I, 3H) [z|r|h̃];
    wh: (H, 3H); b: (3H,).  Returns h'.  ``interpret=None`` lets the
    platform decide (``kernels.platform.resolve_interpret``)."""
    B, I = x.shape
    H = h.shape[-1]
    bt = min(block_b, B)
    ht = min(block_h, H)
    assert B % bt == 0 and H % ht == 0, (B, H, bt, ht)
    wx3 = wx.reshape(I, 3, H).transpose(1, 0, 2)         # (3, I, H)
    wh3 = wh.reshape(H, 3, H).transpose(1, 0, 2)         # (3, H, H)
    b3 = b.reshape(3, 1, H)

    grid = (B // bt, H // ht)
    return pl.pallas_call(
        _gru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, I), lambda bi, hj: (bi, 0)),
            pl.BlockSpec((bt, H), lambda bi, hj: (bi, 0)),
            pl.BlockSpec((bt, ht), lambda bi, hj: (bi, hj)),
            pl.BlockSpec((3, I, ht), lambda bi, hj: (0, 0, hj)),
            pl.BlockSpec((3, H, ht), lambda bi, hj: (0, 0, hj)),
            pl.BlockSpec((3, 1, ht), lambda bi, hj: (0, 0, hj)),
        ],
        out_specs=pl.BlockSpec((bt, ht), lambda bi, hj: (bi, hj)),
        out_shape=jax.ShapeDtypeStruct((B, H), h.dtype),
        interpret=resolve_interpret(interpret),
    )(x, h, h, wx3, wh3, b3)
