"""Forecasts completed in the window over the window's seconds."""


def read(rec):
    return rec["served"] / rec["window_s"]
