"""The FLOP counts against a hand count of each cell's matmuls."""
import json
from pathlib import Path

from bench.harness import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_lstm64_hand_count():
    # per step: x (1) @ wx (1 x 256) and h (64) @ wh (64 x 256), 2 FLOPs a
    # multiply-add; 8 steps; head 64 x 4
    step = 2 * 1 * 256 + 2 * 64 * 256
    fwd = 8 * step + 2 * 64 * 4
    assert flops.forward_flops(_model("lstm64")) == fwd == 266_752
    assert flops.train_flops(_model("lstm64")) == 3 * fwd == 800_256


def test_gru64_hand_count():
    step = 2 * 1 * 192 + 2 * 64 * 192
    fwd = 8 * step + 2 * 64 * 4
    assert flops.forward_flops(_model("gru64")) == fwd == 200_192
    assert flops.train_flops(_model("gru64")) == 3 * fwd == 600_576


def test_second_layer_takes_hidden_input():
    m = {**_model("lstm64"), "n_layers": 2}
    one = flops.forward_flops(_model("lstm64"))
    assert flops.forward_flops(m) == one + 8 * (2 * 64 * 256 + 2 * 64 * 256)
