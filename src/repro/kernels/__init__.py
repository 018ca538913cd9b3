"""Pallas TPU kernels for the compute hot-spots: fused LSTM/GRU cells (the
paper's edge training inner loop) and flash attention (the assigned archs'
prefill).  Compiled by Mosaic on a TPU and interpreted on any other backend,
chosen at trace time from the platform (``platform.py``); validated against
the ref.py oracles in interpret mode on CPU and compiled for v5e by
``tests/test_tpu_compile.py``."""
from repro.kernels import ops, ref
