"""Mean time from a fault's eviction of a request to its re-admission,
over the faults the engine recovered in the window."""


def read(run):
    c = run["counters"]
    if not c.get("recoveries"):
        return None
    return c["recovery_ms_sum"] / c["recoveries"]
