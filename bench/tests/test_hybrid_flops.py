"""The hybrid forecaster's FLOP count against a hand count of its
matmuls, and its configuration against the catalog keys it copies."""
import json
from pathlib import Path

from bench.harness import hybrid_flops

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "granite4hmicro_fc.json").read_text())


def test_granite4hmicro_fc_hand_count():
    # per position, 2 FLOPs a multiply-add; d 2,048, d_in 4,096, 64 heads
    # of 64, state 128, one group, chunk 256, window 2,048
    in_out = 2 * 2048 * 8512 + 2 * 4096 * 2048         # in_proj, out_proj
    ssd = (2 * 256 * 128                               # C B^T, one group
           + 2 * 64 * 256 * 64                         # scores x, 64 heads
           + 2 * 2 * 64 * 64 * 128)                    # state out, update
    mamba = in_out + ssd
    mlp = 3 * 2 * 2048 * 8192                          # gate, up, down
    attn = (2 * 2048 * (32 + 8 + 8) * 64 + 2 * 32 * 64 * 2048   # q k v, o
            + 2 * 2 * 32 * 64 * 2048)                  # scores, values
    per_position = 9 * (mamba + mlp) + (attn + mlp) + 2 * 2048 + 2 * 2048 * 4
    assert per_position == 1_547_522_048
    fwd = 2048 * per_position
    m = CONFIG["model"]
    assert hybrid_flops.forward_flops(m) == fwd == 3_169_325_154_304
    assert hybrid_flops.train_flops(m) == 3 * fwd


def test_about_six_flops_a_parameter_a_position():
    # the projections and MLPs are 6 N a trained position; attention
    # scores and the SSD add 3 %
    per_position = hybrid_flops.train_flops(CONFIG["model"]) / 2048
    assert 1.0 < per_position / (6 * CONFIG["num_params"]) < 1.05


def test_configuration_keeps_the_published_widths():
    m = CONFIG["model"]
    assert CONFIG["hidden_size"] == m["d_model"] == 2048
    assert CONFIG["intermediate_size"] == m["d_ff"] == 8192
    assert CONFIG["num_attention_heads"] == m["n_heads"] == 32
    assert CONFIG["num_key_value_heads"] == m["n_kv_heads"] == 8
    assert CONFIG["mamba_d_head"] == m["ssm"]["head_dim"] == 64
    assert CONFIG["mamba_d_state"] == m["ssm"]["state_dim"] == 128
    assert CONFIG["mamba_expand"] == m["ssm"]["expand"] == 2
    assert CONFIG["mamba_n_heads"] * 64 == 2 * 2048
    assert CONFIG["mamba_d_conv"] == m["ssm"]["conv_width"] == 4
    assert CONFIG["mamba_chunk_size"] == m["ssm"]["chunk_size"] == 256
    assert CONFIG["mamba_n_groups"] == m["ssm"]["n_groups"] == 1
    assert CONFIG["rms_norm_eps"] == m["norm_eps"]
    for k in ("attention_multiplier", "residual_multiplier",
              "embedding_multiplier", "logits_scaling"):
        assert CONFIG[k] == m[k], k
    lo, hi = CONFIG["layers_kept"]
    assert m["layer_types"] == CONFIG["layer_types"][lo:hi]
    assert CONFIG["num_hidden_layers"] == len(m["layer_types"]) == 10
    assert CONFIG["reduced"] == ["num_hidden_layers"]
