"""Matmul FLOPs of the hybrid Mamba2/attention forecaster, from its shapes.

One sample is one window: ``lookback`` positions through the value
embedding, the layers, the final norm and the head.  Counted per position,
two FLOPs a multiply-add:

- Mamba2 layer: ``in_proj`` (d -> 2 d_in + 2 G N + nh) and ``out_proj``
  (d_in -> d); the chunked SSD's products over a chunk of Q positions:
  C·B^T within the chunk (per group, Q N), the masked scores times x
  (per head, Q hd), the output from the carried state (per head, hd N) and
  the state update (per head, hd N).  The chunk's square is counted whole,
  as the usual convention counts attention's (Chowdhery et al. 2022), and
  a chunk is never longer than the window.
- Attention layer: the q, k, v and o projections, and per head the scores
  and the weighted values over the window (hd S each, the square whole).
- Every layer's MLP: gate and up (d -> d_ff each) and down (d_ff -> d).
- The value embedding (1 -> d) and the head (d -> horizon).

Training counts three passes: the forward pass and a backward pass of
twice its matmuls.  Rematerialised recomputation is not counted: it is
work the model does not need.  Element-wise work (norms, the conv, gates,
exps, softmax) is left out: "matmuls only".
"""
from __future__ import annotations


def _mamba(cfg: dict, S: int) -> int:
    s, d = cfg["ssm"], cfg["d_model"]
    d_in = s["expand"] * d
    hd, N, G = s["head_dim"], s["state_dim"], s["n_groups"]
    nh = d_in // hd
    Q = min(s["chunk_size"], S)
    proj = 2 * d * (2 * d_in + 2 * G * N + nh) + 2 * d_in * d
    ssd = 2 * G * Q * N + 2 * nh * Q * hd + 2 * (2 * nh * hd * N)
    return proj + ssd


def _attention(cfg: dict, S: int) -> int:
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    proj = 2 * d * (H + 2 * Hkv) * hd + 2 * H * hd * d
    return proj + 2 * (2 * H * hd * S)


def forward_flops(cfg: dict) -> int:
    """Matmul FLOPs of one window's forward pass."""
    S, d = cfg["lookback"], cfg["d_model"]
    mlp = 3 * 2 * d * cfg["d_ff"]
    per_position = sum((_mamba(cfg, S) if kind == "mamba"
                        else _attention(cfg, S)) + mlp
                       for kind in cfg["layer_types"])
    per_position += 2 * d + 2 * d * cfg["horizon"]
    return S * per_position


def train_flops(cfg: dict) -> int:
    """Matmul FLOPs of one window in one SGD step: forward + backward."""
    return 3 * forward_flops(cfg)
