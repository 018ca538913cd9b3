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
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import gru_cell as _gru
from repro.kernels import lstm_cell as _lstm
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
