"""Share of the chip's bf16 peak the serving step reached: forward matmul
FLOPs of the real rows served, over the peak times the engine's flush
seconds."""
from bench.harness import flops, peaks


def read(rec):
    if not rec["busy_s"]:
        return None
    done = rec["served"] * flops.forward_flops(rec["model"])
    peak = peaks.peak(rec["device_kind"])["bf16_flops"]
    return done / (peak * rec["busy_s"]) * 100.0
