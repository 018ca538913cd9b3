"""Matmul FLOPs of the paper's recurrent forecasters, from their shapes.

One sample is one look-back window through the cell and the linear head.
The forward pass per time step multiplies the input (``input_dim``) and the
hidden state (``hidden_dim``) by the gate matrices (``gates * hidden_dim``
wide: 4 for the LSTM, 3 for the GRU); the head multiplies the last hidden
state by a ``hidden_dim x horizon`` matrix.  Training counts the usual
three passes: the forward pass and a backward pass of twice its matmuls
(one for the weights' gradient, one for the input's).  Element-wise gate
arithmetic is left out: "matmuls only".
"""
from __future__ import annotations

GATES = {"lstm": 4, "gru": 3}


def forward_flops(cfg: dict) -> int:
    """Matmul FLOPs of one window's forward pass."""
    H = cfg["hidden_dim"]
    g = GATES[cfg["cell"]] * H
    per_step = sum(2 * (cfg["input_dim"] if l == 0 else H) * g + 2 * H * g
                   for l in range(cfg["n_layers"]))
    return cfg["lookback"] * per_step + 2 * H * cfg["horizon"]


def train_flops(cfg: dict) -> int:
    """Matmul FLOPs of one window in one SGD step: forward + backward."""
    return 3 * forward_flops(cfg)
