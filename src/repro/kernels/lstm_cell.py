"""Fused LSTM cell — Pallas TPU kernel.

The paper's edge hot-spot is the recurrent cell (Pi clients spend 70–100 s
per round in LSTM training).  On TPU the win is fusing BOTH matmuls and all
four gate nonlinearities into one kernel so the (B, 4H) pre-activation never
round-trips to HBM between the matmul and the gates: HBM traffic drops from
3·(B·4H) intermediate reads/writes to just the final (h', c') writes.

Tiling: grid (B/bt, H/ht).  The jitted wrapper relays the public
``(I, 4H)`` / ``(H, 4H)`` / ``(4H,)`` weights out gate-first as
``(4, I, H)`` / ``(4, H, H)`` / ``(4, 1, H)`` before the ``pallas_call``,
so the kernel computes each gate with its own 2-D ``jnp.dot`` on a
``(I, ht)`` / ``(H, ht)`` weight slab and never reshapes across the tiled
(sublane, lane) dims — the Mosaic compiler refuses such shape casts.  A
hidden tile selects a contiguous H-slice of every gate; the h·Wh matmul
needs ALL of h, so the h block is (bt, H) — for forecaster-scale H (≤1024)
this sits comfortably in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def _lstm_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref,
                 h_out_ref, c_out_ref):
    x = x_ref[...]                                       # (bt, I)
    h = h_ref[...]                                       # (bt, H)

    def gate(k):                                         # (bt, ht) f32
        return (jnp.dot(x, wx_ref[k], preferred_element_type=jnp.float32)
                + jnp.dot(h, wh_ref[k], preferred_element_type=jnp.float32)
                + b_ref[k].astype(jnp.float32))

    i = jax.nn.sigmoid(gate(0))
    f = jax.nn.sigmoid(gate(1))
    g = jnp.tanh(gate(2))
    o = jax.nn.sigmoid(gate(3))
    c_new = f * c_ref[...].astype(jnp.float32) + i * g
    h_out_ref[...] = (o * jnp.tanh(c_new)).astype(h_out_ref.dtype)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_h", "interpret"))
def lstm_cell(x, h, c, wx, wh, b, *, block_b: int = 128, block_h: int = 128,
              interpret: Optional[bool] = None):
    """Fused LSTM step.  x: (B, I); h, c: (B, H); wx: (I, 4H) [i|f|g|o];
    wh: (H, 4H); b: (4H,).  Returns (h', c').  ``interpret=None`` lets the
    platform decide (``kernels.platform.resolve_interpret``)."""
    B, I = x.shape
    H = h.shape[-1]
    bt = min(block_b, B)
    ht = min(block_h, H)
    assert B % bt == 0 and H % ht == 0, (B, H, bt, ht)
    wx3 = wx.reshape(I, 4, H).transpose(1, 0, 2)         # (4, I, H)
    wh3 = wh.reshape(H, 4, H).transpose(1, 0, 2)         # (4, H, H)
    b3 = b.reshape(4, 1, H)

    grid = (B // bt, H // ht)
    return pl.pallas_call(
        _lstm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, I), lambda bi, hj: (bi, 0)),
            pl.BlockSpec((bt, H), lambda bi, hj: (bi, 0)),
            pl.BlockSpec((bt, ht), lambda bi, hj: (bi, hj)),
            pl.BlockSpec((4, I, ht), lambda bi, hj: (0, 0, hj)),
            pl.BlockSpec((4, H, ht), lambda bi, hj: (0, 0, hj)),
            pl.BlockSpec((4, 1, ht), lambda bi, hj: (0, 0, hj)),
        ],
        out_specs=[
            pl.BlockSpec((bt, ht), lambda bi, hj: (bi, hj)),
            pl.BlockSpec((bt, ht), lambda bi, hj: (bi, hj)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H), h.dtype),
            jax.ShapeDtypeStruct((B, H), c.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(x, h, c, wx3, wh3, b3)
