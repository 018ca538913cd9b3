"""Seconds from the start of the process to the start of the window:
JAX's start, data and weights, warm-up and compilation."""


def read(rec):
    return rec["setup_s"]
