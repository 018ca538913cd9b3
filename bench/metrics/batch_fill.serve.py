"""Share of padded bucket rows that held a real request, over the
window's flushes (the engine's counters)."""


def read(rec):
    return None if rec["fill"] is None else rec["fill"] * 100.0
