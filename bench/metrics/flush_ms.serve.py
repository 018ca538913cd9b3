"""Mean time of one engine flush in the window: the engine's own
``EngineStats.busy_s`` (blocked on the result) over its flush count."""


def read(rec):
    return rec["busy_s"] / rec["flushes"] * 1e3 if rec["flushes"] else None
