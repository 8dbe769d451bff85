"""Mean wall time of one engine step over the window: the engine's own
per-step time (decode launch and its fault sync), summed, over steps."""


def read(run):
    c = run["counters"]
    if not c.get("engine_steps"):
        return None
    return c["decode_ms_sum"] / c["engine_steps"]
