"""99th percentile of how late the load generator submitted a request
after it was due."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["lag_s"], 99)) * 1e3
