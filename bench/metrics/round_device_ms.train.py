"""Device time of the round program per round, from the profiler trace:
the executions of the jitted round (``pipeline_round`` on one chip, the
shard_map round body on a mesh), averaged over the cell's chips.  A traced
training window in which no round program ran is an error."""
ROUND_PROGRAMS = ("pipeline_round", "round_body")


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    s = sum(v for k, v in tr["modules"].items()
            if any(p in k for p in ROUND_PROGRAMS))
    if not s:
        raise ValueError(f"no round program {ROUND_PROGRAMS} among the "
                         f"traced modules {sorted(tr['modules'])[:20]}")
    return s / rec["rounds"] * 1e3
