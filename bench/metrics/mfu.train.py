"""Share of the chips' bf16 peak the training window reached: forward and
backward matmul FLOPs of every sample trained, over chips times peak
times the window's seconds."""
from bench.harness import flops, peaks


def read(rec):
    done = rec["samples"] * flops.train_flops(rec["model"])
    peak = peaks.peak(rec["device_kind"])["bf16_flops"]
    return done / (rec["chips"] * peak * rec["window_s"]) * 100.0
