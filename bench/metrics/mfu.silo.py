"""Share of the chips' bf16 peak the training window reached: the hybrid
backbone's forward and backward matmul FLOPs of every window trained
(``bench/harness/hybrid_flops.py``), over chips times peak times the
window's seconds."""
from bench.harness import hybrid_flops, peaks


def read(rec):
    done = rec["samples"] * hybrid_flops.train_flops(rec["model"])
    peak = peaks.peak(rec["device_kind"])["bf16_flops"]
    return done / (rec["chips"] * peak * rec["window_s"]) * 100.0
