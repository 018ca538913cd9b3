"""Attention: GQA (+QKV bias, qk-norm, RoPE, sliding window) and MLA.

Three entry points per variant:
  * ``init_*``            parameter init
  * ``*_forward``         full-sequence (train / prefill); optionally fills a cache
  * ``*_decode``          one-token step against a cache

Cache layout (GQA): ``{"k": (B, W, Hkv, hd), "v": ..., "pos_ids": (W,)}`` where
``W`` is the cache capacity (seq_len, or the sliding window).  ``pos_ids``
stores absolute positions (-1 = empty) so sliding-window decode masks correctly
after wraparound.  The cache's second axis is *sequence*-sharded on the mesh
(logical axis "cache_seq") so GQA archs with few KV heads still shard 16-way.

MLA (DeepSeek-V3): caches the compressed latent ``c_kv`` (+ shared ``k_rope``)
and uses the *absorbed* formulation for decode (q absorbed through W_uk, output
absorbed through W_uv), which is what makes 128-head MLA decode tractable.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_norm
from repro.sharding import constrain, constrain_heads

NEG_INF = -1e9


# ===================================================================== GQA
def init_attention(key, cfg: ModelConfig, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, H * hd, dtype=dtype),
        "wk": dense_init(ks[1], d, Hkv * hd, dtype=dtype),
        "wv": dense_init(ks[2], d, Hkv * hd, dtype=dtype),
        "wo": dense_init(ks[3], H * hd, d, scale=(H * hd) ** -0.5, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Hkv * hd,), dtype)
        p["bv"] = jnp.zeros((Hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(params, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = constrain(jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(x.dtype)),
                  "batch", None, "act_ff")
    k = constrain(jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(x.dtype)),
                  "batch", None, "act_ff")
    v = constrain(jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(x.dtype)),
                  "batch", None, "act_ff")
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B,S,H,hd), k: (B,W,Hkv,hd) -> (B,S,H,W) with KV-head grouping."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, hd)
    s = jnp.einsum("bskgh,bwkh->bskgw", qg, k)
    return s.reshape(B, S, H, k.shape[1])


def _gqa_out(w, v):
    """w: (B,S,H,W), v: (B,W,Hkv,hd) -> (B,S,H,hd)."""
    B, S, H, W = w.shape
    Hkv = v.shape[2]
    G = H // Hkv
    wg = w.reshape(B, S, Hkv, G, W)
    o = jnp.einsum("bskgw,bwkh->bskgh", wg, v)
    return o.reshape(B, S, H, v.shape[-1])


Q_CHUNK = 512          # q-block size for the chunked (memory-bounded) path
CHUNK_THRESHOLD = 4096  # use chunked attention for sequences >= this

# route full-sequence attention through the Pallas flash kernel
# (repro.kernels.flash_attention).  On TPU this is the production path; on
# CPU it runs in interpret mode (slow -- tests only), so it defaults off.
USE_FLASH_KERNEL = bool(os.environ.get("REPRO_FLASH"))


def _causal_attend(q, k, v, scale, window: int, dtype, q_chunk: int = 0):
    """Causal attention, q-chunked above CHUNK_THRESHOLD to bound the score
    materialization at (B, Q_CHUNK, H, S) instead of (B, S, H, S).
    ``q_chunk`` > 0 chunks at that many query rows whenever it divides S."""
    B, S = q.shape[:2]
    if USE_FLASH_KERNEL and S % 128 == 0 and v.shape[-1] == q.shape[-1]:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True, window=window,
                                    scale=scale)

    @jax.checkpoint
    def block(args):
        # checkpointed: the (B, qc, H, S) score/weight tensors are transient
        # in BOTH passes — backward recomputes them chunk by chunk instead of
        # stacking one copy per chunk in the lax.map residuals
        qb, off = args                                  # qb: (B, qc, H, hd)
        qc = qb.shape[1]
        s = _gqa_scores(qb, k) * scale                  # (B,qc,H,S)
        i = off + jnp.arange(qc)[:, None]
        j = jnp.arange(S)[None, :]
        mask = j <= i
        if window:
            mask &= j > i - window
        s = jnp.where(mask[:, None, :][None], s.astype(jnp.float32), NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(dtype)
        return constrain_heads(_gqa_out(w, v))          # (B,qc,H,hd)

    qc = q_chunk or Q_CHUNK
    if (S <= qc or S % qc) or (not q_chunk and S < CHUNK_THRESHOLD):
        return block((q, 0))
    n = S // qc
    qb = q.reshape(B, n, qc, *q.shape[2:]).swapaxes(0, 1)
    offs = jnp.arange(n, dtype=jnp.int32) * qc
    ob = jax.lax.map(block, (qb, offs))                 # (n,B,qc,H,hd_v)
    return ob.swapaxes(0, 1).reshape(B, S, ob.shape[-2], ob.shape[-1])


def attention_forward(params, x, cfg: ModelConfig, *, cache=None,
                      window: int = 0):
    """Full-sequence causal attention. Fills ``cache`` in place-of (returns new)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(params, x, cfg, positions)
    q = constrain_heads(q)
    k = constrain_heads(k)
    v = constrain_heads(v)

    o = _causal_attend(q, k, v, hd ** -0.5, window, x.dtype)
    o = constrain_heads(o)
    o = o.reshape(B, S, cfg.n_heads * hd)
    out = jnp.einsum("bsh,hd->bsd", o, params["wo"].astype(x.dtype))

    new_cache = None
    if cache is not None:
        W = cache["k"].shape[1]
        kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, 0, 0))
        pos_ids = cache["pos_ids"]
        pos_ids = jax.lax.dynamic_update_slice(
            pos_ids, jnp.arange(S, dtype=pos_ids.dtype), (0,))
        new_cache = {"k": kc, "v": vc, "pos_ids": pos_ids}
    return out, new_cache


def nope_attention(params, x, cfg: ModelConfig, *, scale: float,
                   q_chunk: int = 0):
    """Full-sequence causal GQA with no position embedding (NoPE: position
    reaches the model through the causal mask and the other mixers only),
    scores scaled by ``scale`` in place of 1/sqrt(head_dim), no biases and
    no qk-norm.  x: (B, S, d) -> (B, S, d); ``init_attention``'s params."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(x.dtype))
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    o = _causal_attend(q, k, v, scale, 0, x.dtype, q_chunk=q_chunk)
    o = o.reshape(B, S, cfg.n_heads * hd)
    return jnp.einsum("bsh,hd->bsd", o, params["wo"].astype(x.dtype))


def attention_decode(params, x, cache, pos, cfg: ModelConfig, *, window: int = 0):
    """One-token decode. x: (B,1,d); pos: scalar int32 (tokens already cached)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions)   # q:(B,1,H,hd) k:(B,1,Hkv,hd)

    W = cache["k"].shape[1]
    slot = (pos % W) if window else jnp.minimum(pos, W - 1)
    kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    pos_ids = jax.lax.dynamic_update_slice(
        cache["pos_ids"], jnp.array([pos], cache["pos_ids"].dtype), (slot,))
    kc = constrain(kc, "batch", "cache_seq", None, None)
    vc = constrain(vc, "batch", "cache_seq", None, None)

    scores = _gqa_scores(q, kc) * (hd ** -0.5)          # (B,1,H,W)
    valid = (pos_ids >= 0) & (pos_ids <= pos)
    if window:
        valid &= pos_ids > pos - window
    scores = jnp.where(valid[None, None, None, :], scores.astype(jnp.float32),
                       NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_out(w, vc).reshape(B, 1, cfg.n_heads * hd)
    out = jnp.einsum("bsh,hd->bsd", o, params["wo"].astype(x.dtype))
    return out, {"k": kc, "v": vc, "pos_ids": pos_ids}


def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, capacity, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, capacity, cfg.n_kv_heads, hd), dtype),
        "pos_ids": jnp.full((capacity,), -1, jnp.int32),
    }


# ===================================================================== MLA
def init_mla(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    return {
        "wq_down": dense_init(ks[0], d, m.q_lora_rank, dtype=dtype),
        "wq_up": dense_init(ks[1], m.q_lora_rank,
                            H * (m.qk_nope_dim + m.qk_rope_dim), dtype=dtype),
        "wkv_down": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_dim, dtype=dtype),
        "wk_up": dense_init(ks[3], m.kv_lora_rank, H * m.qk_nope_dim, dtype=dtype),
        "wv_up": dense_init(ks[4], m.kv_lora_rank, H * m.v_head_dim, dtype=dtype),
        "wo": dense_init(ks[5], H * m.v_head_dim, d,
                         scale=(H * m.v_head_dim) ** -0.5, dtype=dtype),
        "q_ln": jnp.ones((m.q_lora_rank,), dtype),
        "kv_ln": jnp.ones((m.kv_lora_rank,), dtype),
    }


def _mla_q(params, x, m: MLAConfig, H, positions, eps):
    B, S, _ = x.shape
    cq = jnp.einsum("bsd,dr->bsr", x, params["wq_down"].astype(x.dtype))
    cq = rms_norm(cq, params["q_ln"], eps)
    q = jnp.einsum("bsr,rh->bsh", cq, params["wq_up"].astype(x.dtype))
    q = q.reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, 10000.0)
    return q_nope, q_rope


def _mla_ckv(params, x, m: MLAConfig, positions, eps):
    ckv = jnp.einsum("bsd,dr->bsr", x, params["wkv_down"].astype(x.dtype))
    c_kv, k_rope = ckv[..., :m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c_kv = rms_norm(c_kv, params["kv_ln"], eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, 10000.0)[:, :, 0, :]
    return c_kv, k_rope


def mla_forward(params, x, cfg: ModelConfig, *, cache=None, window: int = 0):
    """Full-sequence MLA (non-absorbed: expand k/v, standard attention)."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q_nope, q_rope = _mla_q(params, x, m, H, positions, cfg.norm_eps)
    c_kv, k_rope = _mla_ckv(params, x, m, positions, cfg.norm_eps)

    k_nope = jnp.einsum("bsr,rh->bsh", c_kv, params["wk_up"].astype(x.dtype))
    k_nope = k_nope.reshape(B, S, H, m.qk_nope_dim)
    v = jnp.einsum("bsr,rh->bsh", c_kv, params["wv_up"].astype(x.dtype))
    v = v.reshape(B, S, H, m.v_head_dim)
    q_nope = constrain(q_nope, "batch", None, "act_heads", None)

    # fold q_rope/k_rope into the head dim so the chunked GQA path applies
    q_all = jnp.concatenate(
        [q_nope, q_rope], axis=-1)                      # (B,S,H,nope+rope)
    k_all = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_dim))], axis=-1)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    o = _causal_attend(q_all, k_all, v, scale, window, x.dtype)
    o = o.reshape(B, S, H * m.v_head_dim)
    out = jnp.einsum("bsh,hd->bsd", o, params["wo"].astype(x.dtype))

    new_cache = None
    if cache is not None:
        ckv_c = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0))
        kr_c = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, 0, 0))
        pos_ids = jax.lax.dynamic_update_slice(
            cache["pos_ids"], jnp.arange(S, dtype=jnp.int32), (0,))
        new_cache = {"c_kv": ckv_c, "k_rope": kr_c, "pos_ids": pos_ids}
    return out, new_cache


def mla_decode(params, x, cache, pos, cfg: ModelConfig, *, window: int = 0):
    """Absorbed one-token MLA decode against the latent cache."""
    m, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q_nope, q_rope = _mla_q(params, x, m, H, positions, cfg.norm_eps)  # (B,1,H,*)
    c_kv_new, k_rope_new = _mla_ckv(params, x, m, positions, cfg.norm_eps)

    W = cache["c_kv"].shape[1]
    slot = (pos % W) if window else jnp.minimum(pos, W - 1)
    c_kv = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv_new.astype(cache["c_kv"].dtype), (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope_new.astype(cache["k_rope"].dtype), (0, slot, 0))
    pos_ids = jax.lax.dynamic_update_slice(
        cache["pos_ids"], jnp.array([pos], jnp.int32), (slot,))
    c_kv = constrain(c_kv, "batch", "cache_seq", None)
    k_rope = constrain(k_rope, "batch", "cache_seq", None)

    # absorb q through W_uk:  q_abs[b,h,r] = sum_c q_nope[b,h,c] * Wk_up[r, h, c]
    wk_up = params["wk_up"].astype(x.dtype).reshape(m.kv_lora_rank, H, m.qk_nope_dim)
    q_abs = jnp.einsum("bhc,rhc->bhr", q_nope[:, 0], wk_up)            # (B,H,r)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    scores = (jnp.einsum("bhr,bwr->bhw", q_abs, c_kv)
              + jnp.einsum("bhc,bwc->bhw", q_rope[:, 0], k_rope)) * scale
    valid = (pos_ids >= 0) & (pos_ids <= pos)
    if window:
        valid &= pos_ids > pos - window
    scores = jnp.where(valid[None, None, :], scores.astype(jnp.float32), NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhw,bwr->bhr", w, c_kv)                        # (B,H,r)
    # absorb output through W_uv
    wv_up = params["wv_up"].astype(x.dtype).reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wv_up).reshape(B, 1, H * m.v_head_dim)
    out = jnp.einsum("bsh,hd->bsd", o, params["wo"].astype(x.dtype))
    return out, {"c_kv": c_kv, "k_rope": k_rope, "pos_ids": pos_ids}


def init_mla_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, capacity, m.qk_rope_dim), dtype),
        "pos_ids": jnp.full((capacity,), -1, jnp.int32),
    }
