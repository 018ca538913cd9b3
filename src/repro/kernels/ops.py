"""Jit'd wrappers that route model code through the Pallas kernels.

Whether a kernel is compiled or interpreted is decided when it is traced,
from the platform (``kernels/platform.py``): compiled by Mosaic on a TPU,
interpreted (Python-level execution of the kernel body, correctness only)
on any other backend.  No wrapper here takes an ``interpret`` argument.

The recurrent-cell wrappers are differentiable: ``pallas_call`` has no
autodiff rule, so each cell carries a ``custom_vjp`` whose forward is the
fused kernel and whose backward is the VJP of the pure-jnp oracle
(``kernels/ref.py``) — the same math, so gradients are exact.  That is what
lets the federated ``local_update`` (value_and_grad through the forecaster)
run end-to-end with ``cell_impl="pallas"``.

The whole-sequence LSTM kernels (``kernels/lstm_seq.py``) take a client
axis.  Their wrappers here look like one client's recurrence, and a
``custom_vmap`` rule turns a vmap over clients (the round's, and any
around it) into that axis, so one grid step trains a block of clients
rather than one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.kernels import flash_attention as _fa
from repro.kernels import gru_cell as _gru
from repro.kernels import lstm_cell as _lstm
from repro.kernels import lstm_seq as _seq
from repro.kernels import ref as _ref

# TPU tiling: the last two dims of every block must divide by (8, 128) or
# equal the array's dims — batch rows ride the sublanes, hidden units the
# lanes
_SUBLANE, _LANE = 8, 128


@jax.custom_vjp
def _lstm_cell_ad(x, h, c, wx, wh, b):
    return _lstm.lstm_cell(x, h, c, wx, wh, b,
                           block_b=_pick_block(x.shape[0], _SUBLANE),
                           block_h=_pick_block(h.shape[-1], _LANE))


def _lstm_cell_ad_fwd(x, h, c, wx, wh, b):
    return _lstm_cell_ad(x, h, c, wx, wh, b), (x, h, c, wx, wh, b)


def _lstm_cell_ad_bwd(res, ct):
    _, vjp = jax.vjp(_ref.lstm_cell_ref, *res)
    return vjp(ct)


_lstm_cell_ad.defvjp(_lstm_cell_ad_fwd, _lstm_cell_ad_bwd)


@jax.custom_vjp
def _gru_cell_ad(x, h, wx, wh, b):
    return _gru.gru_cell(x, h, wx, wh, b,
                         block_b=_pick_block(x.shape[0], _SUBLANE),
                         block_h=_pick_block(h.shape[-1], _LANE))


def _gru_cell_ad_fwd(x, h, wx, wh, b):
    return _gru_cell_ad(x, h, wx, wh, b), (x, h, wx, wh, b)


def _gru_cell_ad_bwd(res, ct):
    _, vjp = jax.vjp(_ref.gru_cell_ref, *res)
    return vjp(ct)


_gru_cell_ad.defvjp(_gru_cell_ad_fwd, _gru_cell_ad_bwd)


def lstm_cell_fused(x_t, h, c, p, *, block_b=None, block_h=None):
    """Drop-in for models.forecaster.lstm_cell: (x_t, h, c, params) -> (h', c').

    Note the forecaster stores gates [i|f|g|o] in wx/wh — same layout the
    kernel expects.  The default (no explicit blocks) path is differentiable
    via the reference-VJP ``custom_vjp``; explicit block sizes bypass it for
    kernel-tuning benches.
    """
    if block_b or block_h:
        B, H = h.shape
        bb = block_b or _pick_block(B, _SUBLANE)
        bh = block_h or _pick_block(H, _LANE)
        return _lstm.lstm_cell(x_t, h, c, p["wx"], p["wh"], p["b"],
                               block_b=bb, block_h=bh)
    return _lstm_cell_ad(x_t, h, c, p["wx"], p["wh"], p["b"])


def gru_cell_fused(x_t, h, p, *, block_b=None, block_h=None):
    """Drop-in for models.forecaster.gru_cell: (x_t, h, params) -> h'."""
    if block_b or block_h:
        B, H = h.shape
        bb = block_b or _pick_block(B, _SUBLANE)
        bh = block_h or _pick_block(H, _LANE)
        return _gru.gru_cell(x_t, h, p["wx"], p["wh"], p["b"],
                             block_b=bb, block_h=bh)
    return _gru_cell_ad(x_t, h, p["wx"], p["wh"], p["b"])


def _client_axis(kernel):
    """``kernel`` (leading client axis) under a vmap rule that folds the
    vmapped axis into its client axis: a vmap over clients, or a vmap of
    such vmaps, calls the kernel once with every client."""
    fn = custom_batching.custom_vmap(kernel)

    @fn.def_vmap
    def _(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = fn(*[a.reshape((-1,) + a.shape[2:]) for a in args])
        out = jax.tree.map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, jax.tree.map(lambda _: True, out)
    return fn


_lstm_seq_fwd_clients = _client_axis(_seq.lstm_seq_fwd)
_lstm_seq_bwd_clients = _client_axis(_seq.lstm_seq_bwd)


def lstm_seq_forward(x, wx, wh, b):
    """One client's LSTM layer over its look-back, by the fused kernel:
    x (B, L, I), wx (I, 4H), wh (H, 4H), b (4H,) -> h_L (B, H)."""
    return _lstm_seq_fwd_clients(x[None], wx[None], wh[None], b[None])[0]


def lstm_seq_backward(x, wx, wh, b, dh):
    """VJP of :func:`lstm_seq_forward` for the cotangent dh (B, H) of h_L:
    (dx, dwx, dwh, db), by the fused kernel that recomputes the forward
    in VMEM."""
    grads = _lstm_seq_bwd_clients(x[None], wx[None], wh[None], b[None],
                                  dh[None])
    return tuple(g[0] for g in grads)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    block_q=128, block_k=128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, block_q=block_q, block_k=block_k)


def _pick_block(n: int, align: int, target: int = 128) -> int:
    """Block size along a dim of ``n``: the largest multiple of ``align``
    that divides ``n`` and is ≤ ``target``, else the whole dim (always a
    legal TPU block)."""
    for b in range(min(n, target) // align * align, 0, -align):
        if n % b == 0:
            return b
    return n
