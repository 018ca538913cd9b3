"""99th percentile of the latency of every request due in the window, each
timed from when it was due to when its result arrived."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["latency_s"], 99)) * 1e3
