"""Samples trained per second: every window in every SGD step of every
real client in the window's rounds, over the window's seconds."""


def read(rec):
    return rec["samples"] / rec["window_s"]
