"""Share of the window in which no operation ran on the device, from the
profiler trace, averaged over the cell's chips.  Serves every
``device_idle_share.<kind>`` metric."""


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    return (1.0 - tr["busy_s"] / rec["window_s"]) * 100.0
