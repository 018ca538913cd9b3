"""Decoder-only load forecaster on granite-4.0-h's hybrid block.

Each 15-minute reading is one position.  A linear value embedding lifts
the (B, L) readings to the model width, a stack of hybrid layers mixes
them causally, and a linear head maps every position to the next
``horizon`` readings: (B, L) -> (B, L, horizon), trained on every
position at once, as decoder-only forecasters such as Time-MoE are.

One layer (granite-4.0-h, ``granitemoehybrid``)::

    h   = x + r * mixer(RMSNorm(x))        mixer: Mamba2 or NoPE GQA
    out = h + r * MLP(RMSNorm(h))          MLP: SiLU(gate) * up, down

with r = ``residual_multiplier``; the input is scaled by
``embedding_multiplier`` and the output divided by ``logits_scaling``
after a final RMSNorm.  The Mamba2 mixer is ``models/ssm.py``'s chunked
SSD, the attention mixer ``models/attention.py::nope_attention``, the MLP
``models/layers.py::mlp``.

Consecutive layers of one kind form a segment whose parameters are
stacked on a leading axis and scanned, so a period of the 9:1 pattern
compiles three layer bodies, not ten.  Each layer is rematerialised
(``jax.checkpoint``): the backward pass keeps one (B, L, d) input a layer
and recomputes the rest.  Named scopes ``hybrid/mamba`` (holding
``ssd``), ``hybrid/attention`` and ``hybrid/mlp`` reach the compiled ops'
metadata, where a device trace reads them.

Initialisation, from the key ``init`` is given: layer i draws from
``fold_in(key, i)`` split into (mixer, MLP) keys, each module as its
``init_*`` in ``models/`` states; the embedding's weight and bias (both
normal, scale 1) from the two halves of ``fold_in(key, n_layers)``, the
head (normal, d ** -0.5, zero bias) from ``fold_in(key, n_layers + 1)``;
every RMSNorm weight is one.  The embedding's bias is random so that no
reading embeds to the zero vector: a min-max normalised series reads
exactly 0 at its minimum, and the RMSNorm of a zero vector scales its
gradient by 1/sqrt(eps), which made the first rounds diverge.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import HybridForecasterConfig
from repro.models import attention, layers, ssm

# query rows per attention block: bounds the (B, rows, H, L) scores that
# one block holds in each pass
ATTN_Q_CHUNK = 256


def segments(cfg: HybridForecasterConfig) -> List[Tuple[str, int, int]]:
    """(kind, first layer, layer count) of each run of one layer kind."""
    out: List[Tuple[str, int, int]] = []
    for i, kind in enumerate(cfg.layer_types):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, i, 1))
    return out


def _init_layer(key, kind: str, cfg: HybridForecasterConfig) -> Dict:
    bb = cfg.backbone
    k_mix, k_mlp = jax.random.split(key)
    mixer = (ssm.init_ssm(k_mix, bb) if kind == "mamba"
             else attention.init_attention(k_mix, bb))
    return {"norm1": jnp.ones((cfg.d_model,), jnp.float32), "mixer": mixer,
            "norm2": jnp.ones((cfg.d_model,), jnp.float32),
            "mlp": layers.init_mlp(k_mlp, cfg.d_model, cfg.d_ff)}


@functools.partial(jax.jit, static_argnames=("cfg",))
def init(key, cfg: HybridForecasterConfig) -> Dict:
    """Initial parameters (module docstring); one program, so the stacked
    segments are built in place."""
    segs = []
    for kind, start, n in segments(cfg):
        ls = [_init_layer(jax.random.fold_in(key, start + j), kind, cfg)
              for j in range(n)]
        segs.append(jax.tree.map(lambda *a: jnp.stack(a), *ls))
    n_layers = len(cfg.layer_types)
    ke_w, ke_b = jax.random.split(jax.random.fold_in(key, n_layers))
    kh = jax.random.fold_in(key, n_layers + 1)
    d = cfg.d_model
    return {
        "embed": {"w": layers.dense_init(ke_w, 1, d, scale=1.0),
                  "b": jax.random.normal(ke_b, (d,), jnp.float32)},
        "segments": segs,
        "norm": jnp.ones((d,), jnp.float32),
        "head": {"w": layers.dense_init(kh, d, cfg.horizon),
                 "b": jnp.zeros((cfg.horizon,), jnp.float32)},
    }


def param_shapes(cfg: HybridForecasterConfig) -> Dict:
    """:func:`init`'s tree of shapes and dtypes, with nothing allocated."""
    return jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32), cfg)


def param_template(cfg: HybridForecasterConfig) -> Dict:
    """Zero tree with :func:`init`'s structure, shapes and dtypes."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        param_shapes(cfg))


def _mixer(h, p, *, kind: str, cfg: HybridForecasterConfig):
    with jax.named_scope("hybrid"), jax.named_scope(kind):
        u = layers.rms_norm(h, p["norm1"], cfg.norm_eps)
        if kind == "mamba":
            return ssm.ssm_forward(p["mixer"], u, cfg.backbone)[0]
        return attention.nope_attention(
            p["mixer"], u, cfg.backbone, scale=cfg.attention_multiplier,
            q_chunk=ATTN_Q_CHUNK)


def _mlp(h, p, *, cfg: HybridForecasterConfig):
    with jax.named_scope("hybrid"), jax.named_scope("mlp"):
        return layers.mlp(p["mlp"], layers.rms_norm(h, p["norm2"],
                                                    cfg.norm_eps))


def _layer(h, p, *, kind: str, cfg: HybridForecasterConfig):
    r = cfg.residual_multiplier
    # the mixer and the MLP are rematerialised apart, so the backward pass
    # holds the internals of one of them at a time
    h = h + r * jax.checkpoint(functools.partial(_mixer, kind=kind,
                                                 cfg=cfg))(h, p)
    return h + r * jax.checkpoint(functools.partial(_mlp, cfg=cfg))(h, p)


def forward(params, x, cfg: HybridForecasterConfig):
    """x: (B, L) normalised readings -> (B, L, horizon) forecasts."""
    e = params["embed"]
    h = (x[..., None] @ e["w"] + e["b"]) * cfg.embedding_multiplier
    for (kind, _, _), seg in zip(segments(cfg), params["segments"]):
        body = jax.checkpoint(functools.partial(_layer, kind=kind, cfg=cfg))
        h, _ = jax.lax.scan(lambda c, p, body=body: (body(c, p), None), h,
                            seg)
    h = layers.rms_norm(h, params["norm"], cfg.norm_eps)
    return (h @ params["head"]["w"] + params["head"]["b"]) / cfg.logits_scaling


def loss_fn(params, batch, cfg: HybridForecasterConfig, loss):
    """batch: {"x": (B, L), "y": (B, L, horizon)} -> the loss over every
    position."""
    return loss(forward(params, batch["x"], cfg), batch["y"])
