"""The LSTM layer's whole look-back, forward and backward, as Pallas TPU
kernels over blocks of clients.

One federated local step differentiates an L-step LSTM recurrence for every
client of the round.  Run as XLA ops, each timestep's (B, 4H) gate
pre-activations and its h and c states go to HBM and back, forward and
backward: about 3 GB of HBM traffic a step at the R1 cohort, for a step
whose inputs and outputs are under 60 MB.  These kernels keep all of it in
VMEM:

- :func:`lstm_seq_fwd` runs the L steps and writes the last h.
- :func:`lstm_seq_bwd` recomputes the L forward steps into VMEM scratch,
  then back-propagates through them and writes the gradients of the
  inputs, weights and bias.  No per-timestep residual leaves VMEM; the
  recompute costs one more forward.

Both take an explicit client axis (C, ...), each client with its own
weights, and a grid step trains ``block_clients`` clients side by side, so
that the dependent chain of L dots of one client overlaps the others'.

Layout.  Batch rows ride the sublanes.  The gate pre-activations
``z = [i|f|g|o]`` (B, 4H) are two lane blocks of 2H, ``[i|f]`` and
``[g|o]``: with 2H a multiple of 128 lanes every elementwise op runs on
whole vregs and no gate is ever sliced out of its tile.  The states are
carried "paired", 2H wide: c as ``[c|c]`` and h in the upper half of the
matmul operand ``a = [x_t, 0 | h]``.  One step is then

- ``z = a @ [wx; 0; wh] + b`` (the input and the recurrent product in one
  dot; the stacked weight is built once per grid step in VMEM),
- ``[i|f] = σ``, ``[g|o] = [tanh | σ]`` (σ written as ``0.5 tanh(x/2) +
  0.5``: one transcendental a lane),
- ``p = [i|f] * [g|c]``, ``[c'|c'] = p + roll(p, H)``, and
  ``[.|h'] = [g|o] * tanh([c'|c'])``,

where ``roll`` rotates a 2H block by H lanes.  The backward is the same
pairing run in reverse; the transposed product ``dz @ [wx; 0; wh]^T``
yields ``[dx_t, 0 | dh]`` in one dot, and the weight gradient is one
contraction over all L·B rows at the end, ``[x_t, 0 | h]^T dz``.

Precision is XLA's DEFAULT on the backend the kernel runs on: dots take
bfloat16 operands (one MXU pass) with float32 accumulation when compiled
for a TPU, float32 when interpreted; the gate math, c, h and every
gradient accumulator are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

_LANE = 128
# VMEM a grid step may spend on the backward's per-client scratch; the
# block of clients is the largest that fits it (and at most MAX_BLOCK).
# At B 64, L 8, H 64 that is 8 clients (1 MiB each): on a TPU v5e the
# R1 cohort's local step took 0.76 ms so, against 0.84 ms with blocks of
# 4 and 0.81 ms with blocks of 16 or 32.
SCRATCH_BUDGET = 8 * 1024 * 1024
MAX_BLOCK = 16


def fits(hidden: int, lookback: int, input_dim: int) -> bool:
    """Whether the paired layout holds these widths: 2H whole lane tiles,
    and a look-back's inputs within the first H lanes."""
    return (2 * hidden) % _LANE == 0 and lookback * input_dim <= hidden


def scratch_bytes(batch: int, lookback: int, hidden: int) -> int:
    """Backward scratch of one client: per step the matmul operand (2H),
    the gates, later their gradients (4H), and the paired c (2H)."""
    return lookback * batch * 8 * hidden * 4


def block_clients(n_clients: int, batch: int, lookback: int,
                  hidden: int) -> int:
    """Clients a grid step trains side by side, from the shapes."""
    fit = SCRATCH_BUDGET // scratch_bytes(batch, lookback, hidden)
    return max(1, min(n_clients, MAX_BLOCK, fit))


# ------------------------------------------------------------------ cell
def _halves(H):
    """Lane masks of a paired (.., 2H) value: the lower half of each
    H-wide block pair."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2 * H), 2)
    return lane < H


def _stack_weights(wx, wh, H):
    """[wx; 0; wh] (Cb, 2H, 4H): rows 0..I the input weights, rows H..2H
    the recurrent ones."""
    Cb, I, G = wx.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (1, H, G), 1)
    top = jnp.zeros((Cb, H, G), jnp.float32)
    for i in range(I):
        top = jnp.where(row == i, wx[:, i:i + 1, :], top)
    return jnp.concatenate([top, wh], axis=1)


def _operand(xs, t, I, hd, lo, H):
    """``a = [x_t, 0 | h]``: the step's inputs rotated to lanes 0..I."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2 * H), 2)
    xt = pltpu.roll(xs, (2 * H - t * I) % (2 * H), 2) if t else xs
    return jnp.where(lane < I, xt, jnp.where(lo, 0.0, hd))


def _dot(a, b, contract, dot_dtype):
    """Per-client dot over the leading client axis, float32 accumulation.

    One 2-D dot a client, stacked: Mosaic refuses a batched dot whose
    result takes a broadcast add and then a lane slice."""
    dims = (tuple(d - 1 for d in contract[0]),
            tuple(d - 1 for d in contract[1]))
    return jnp.stack([jax.lax.dot_general(
        a[c].astype(dot_dtype), b[c].astype(dot_dtype), (dims, ((), ())),
        preferred_element_type=jnp.float32) for c in range(a.shape[0])])


def _cell(a, cd, w, bias, lo, H, dot_dtype):
    """One forward step on paired states; returns the gates and the new
    ``[c|c]`` and ``[.|h]``."""
    z = _dot(a, w, ((2,), (1,)), dot_dtype) + bias       # (Cb, B, 4H)
    t0 = 0.5 * jnp.tanh(0.5 * z[..., :2 * H]) + 0.5      # [i | f]
    th = jnp.tanh(jnp.where(lo, 1.0, 0.5) * z[..., 2 * H:])
    t1 = jnp.where(lo, th, 0.5 * th + 0.5)               # [g | o]
    p = t0 * jnp.where(lo, t1, cd)                       # [i g | f c]
    cd = p + pltpu.roll(p, H, 2)                         # [c' | c']
    return t0, t1, cd, t1 * jnp.tanh(cd)                 # [. | h']


# ------------------------------------------------------------------ kernels
def _fwd_kernel(x_ref, wx_ref, wh_ref, b_ref, h_ref, *, L, I, H, dot_dtype):
    lo = _halves(H)
    w = _stack_weights(wx_ref[...], wh_ref[...], H)
    bias, xs = b_ref[...], x_ref[...]
    hd = cd = jnp.zeros(h_ref.shape, jnp.float32)
    for t in range(L):
        a = _operand(xs, t, I, hd, lo, H)
        _, _, cd, hd = _cell(a, cd, w, bias, lo, H, dot_dtype)
    h_ref[...] = hd


def _bwd_kernel(x_ref, wx_ref, wh_ref, b_ref, dh_ref,
                dx_ref, dwx_ref, dwh_ref, db_ref,
                a_ref, g_ref, c_ref, *, L, I, H, dot_dtype):
    B = x_ref.shape[1]
    lo = _halves(H)
    w = _stack_weights(wx_ref[...], wh_ref[...], H)
    bias, xs = b_ref[...], x_ref[...]
    # recompute the forward, keeping each step's operand, gates and c
    hd = cd = jnp.zeros(dh_ref.shape, jnp.float32)
    for t in range(L):
        rows = pl.ds(t * B, B)
        a = _operand(xs, t, I, hd, lo, H)
        t0, t1, cd, hd = _cell(a, cd, w, bias, lo, H, dot_dtype)
        a_ref[:, rows, :] = a
        g_ref[:, rows, :2 * H] = t0
        g_ref[:, rows, 2 * H:] = t1
        c_ref[:, rows, :] = cd
    # back through the steps; each step's gate gradients dz replace its
    # gates in g_ref
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2 * H), 2)
    dhd = dh_ref[...]                                    # [0 | dh]
    dcd = jnp.zeros_like(dhd)                            # [dc | dc]
    dx = jnp.zeros_like(dhd)
    for t in reversed(range(L)):
        rows = pl.ds(t * B, B)
        t0 = g_ref[:, rows, :2 * H]
        t1 = g_ref[:, rows, 2 * H:]
        tc = jnp.tanh(c_ref[:, rows, :])
        c_prev = c_ref[:, pl.ds((t - 1) * B, B), :] if t else 0.0
        e = dhd * t1 * (1.0 - tc * tc)                   # [. | dh o (1-tc²)]
        dcd = dcd + jnp.where(lo, pltpu.roll(e, H, 2), e)
        dz0 = dcd * jnp.where(lo, t1, c_prev) * t0 * (1.0 - t0)
        dz1 = (jnp.where(lo, dcd * t0, dhd * tc)
               * jnp.where(lo, 1.0 - t1 * t1, t1 * (1.0 - t1)))
        g_ref[:, rows, :2 * H] = dz0                     # [dz_i | dz_f]
        g_ref[:, rows, 2 * H:] = dz1                     # [dz_g | dz_o]
        dcd = dcd * jnp.where(lo, pltpu.roll(t0, H, 2), t0)     # [f | f]
        dz = g_ref[:, rows, :]
        dhd = _dot(dz, w, ((2,), (2,)), dot_dtype)       # [dx_t, 0 | dh]
        at = pltpu.roll(dhd, t * I, 2) if t else dhd
        dx = jnp.where((lane >= t * I) & (lane < (t + 1) * I), at, dx)
    dw = _dot(a_ref[...], g_ref[...], ((1,), (1,)), dot_dtype)  # (Cb,2H,4H)
    dx_ref[...] = dx
    dwx_ref[...] = dw[:, :I, :]
    dwh_ref[...] = dw[:, H:, :]
    db_ref[...] = jnp.sum(g_ref[...], axis=1, keepdims=True)


# ------------------------------------------------------------------ calls
def _specs(Cb, B, I, H):
    blk = lambda *s: pl.BlockSpec((Cb,) + s, lambda c: (c, 0, 0))
    return blk(B, 2 * H), blk(I, 4 * H), blk(H, 4 * H), blk(1, 4 * H)


def _pad_clients(n, *arrays):
    C = arrays[0].shape[0]
    if n == C:
        return arrays
    return tuple(jnp.pad(a, ((0, n - C),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)


def _layout(x, H):
    """(C, B, L, I) -> (C, B, 2H): the look-back's inputs on lanes t·I + i,
    zero-padded to the paired width."""
    C, B, L, I = x.shape
    return jnp.pad(x.reshape(C, B, L * I).astype(jnp.float32),
                   ((0, 0), (0, 0), (0, 2 * H - L * I)))


def _params(interpret, vmem_bytes):
    return dict(
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes))


def _check(H, L, I):
    if not fits(H, L, I):
        raise ValueError(f"hidden {H}, look-back {L} x input {I}: the "
                         "paired layout needs 2H in whole lane tiles and "
                         "L*I <= H")


@jax.jit
def lstm_seq_fwd(x, wx, wh, b):
    """Last hidden state of an LSTM layer over C clients' look-backs.

    x: (C, B, L, I); wx: (C, I, 4H) [i|f|g|o]; wh: (C, H, 4H); b: (C, 4H).
    Returns h_L (C, B, H), float32, from zero initial states.  A grid step
    takes :func:`block_clients` clients; C need not divide by it.
    """
    C, B, L, I = x.shape
    H = wh.shape[1]
    _check(H, L, I)
    interpret = resolve_interpret()
    Cb = block_clients(C, B, L, H)
    n = -(-C // Cb) * Cb
    xs, wx_, wh_, b_ = _pad_clients(
        n, _layout(x, H), wx.astype(jnp.float32), wh.astype(jnp.float32),
        b.reshape(C, 1, 4 * H).astype(jnp.float32))
    xspec, wxspec, whspec, bspec = _specs(Cb, B, I, H)
    dot_dtype = jnp.float32 if interpret else jnp.bfloat16
    io = 4 * Cb * (B * 2 * H * 2 + (I + H + 1) * 4 * H)
    hd = pl.pallas_call(
        functools.partial(_fwd_kernel, L=L, I=I, H=H, dot_dtype=dot_dtype),
        grid=(n // Cb,),
        in_specs=[xspec, wxspec, whspec, bspec],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct((n, B, 2 * H), jnp.float32),
        **_params(interpret, 2 * io + 8 * 1024 * 1024),
    )(xs, wx_, wh_, b_)
    return hd[:C, :, H:]


@jax.jit
def lstm_seq_bwd(x, wx, wh, b, dh):
    """VJP of :func:`lstm_seq_fwd` at (x, wx, wh, b) for the cotangent dh
    (C, B, H) of h_L.  Returns (dx, dwx, dwh, db), shaped as the inputs,
    float32."""
    C, B, L, I = x.shape
    H = wh.shape[1]
    _check(H, L, I)
    interpret = resolve_interpret()
    Cb = block_clients(C, B, L, H)
    n = -(-C // Cb) * Cb
    dhd = jnp.pad(dh.astype(jnp.float32), ((0, 0), (0, 0), (H, 0)))
    xs, wx_, wh_, b_, dhd = _pad_clients(
        n, _layout(x, H), wx.astype(jnp.float32), wh.astype(jnp.float32),
        b.reshape(C, 1, 4 * H).astype(jnp.float32), dhd)
    xspec, wxspec, whspec, bspec = _specs(Cb, B, I, H)
    dot_dtype = jnp.float32 if interpret else jnp.bfloat16
    scratch = Cb * scratch_bytes(B, L, H)
    io = 4 * Cb * (2 * B * 2 * H * 2 + 2 * (I + H + 1) * 4 * H)
    dxp, dwx, dwh, db = pl.pallas_call(
        functools.partial(_bwd_kernel, L=L, I=I, H=H, dot_dtype=dot_dtype),
        grid=(n // Cb,),
        in_specs=[xspec, wxspec, whspec, bspec, xspec],
        out_specs=[xspec, wxspec, whspec, bspec],
        out_shape=[jax.ShapeDtypeStruct((n, B, 2 * H), jnp.float32),
                   jax.ShapeDtypeStruct((n, I, 4 * H), jnp.float32),
                   jax.ShapeDtypeStruct((n, H, 4 * H), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1, 4 * H), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((Cb, L * B, 2 * H), jnp.float32),
                        pltpu.VMEM((Cb, L * B, 4 * H), jnp.float32),
                        pltpu.VMEM((Cb, L * B, 2 * H), jnp.float32)],
        **_params(interpret, scratch + 2 * io + 16 * 1024 * 1024),
    )(xs, wx_, wh_, b_, dhd)
    dx = dxp[:C, :, :L * I].reshape(C, B, L, I)
    return dx, dwx[:C], dwh[:C], db[:C, 0]
