"""Causal flash attention (online softmax) — Pallas TPU kernel.

Grid (B, Hq, nQ, nK); the innermost K dimension streams key/value blocks
through VMEM while fp32 accumulators (running max m, normalizer l, output
acc) persist in VMEM scratch across K iterations — the Flash-2 schedule
mapped onto the TPU grid.  Blocks fully above the causal diagonal (or fully
outside the sliding window) skip their matmuls via ``pl.when``.

GQA is native: the K/V BlockSpec index map folds the query head onto its
KV group (h → h·Hkv/Hq), so no K/V replication is materialized.

VMEM per step: q (bq·hd) + k,v (2·bk·hd) + scores (bq·bk) + scratch
(bq·(hd+2)) — with bq=bk=128, hd=128 ≈ 160 KB fp32, far under the ~16 MB
VMEM budget; bigger bq amortizes the q load when hd is small.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, block_q, block_k, n_k, causal, window):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level skip: fully causal-masked or fully outside the window
    relevant = True
    if causal:
        relevant = k_start <= q_start + block_q - 1
    if window:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        iq = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        jk = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= jk <= iq
        if window:
            mask &= jk > iq - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret", "scale"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None, block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd) -> (B, S, Hq, hd).
    ``interpret=None`` lets the platform decide."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_q, n_k = S // bq, S // bk

    qt = q.transpose(0, 2, 1, 3)                         # (B, Hq, S, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kv_map = lambda b, h, i, j: (b, h * Hkv // Hq, j, 0)

    kernel = functools.partial(_flash_kernel, scale=scale, block_q=bq,
                               block_k=bk, n_k=n_k, causal=causal,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
