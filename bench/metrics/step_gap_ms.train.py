"""Device idle per traced step that no ``snapshot``, ``fault`` or
``inject`` span covers: the host's turnaround between steps, around the
one blocking fetch of the fault flag (``step.flag_sync``).

The traced window runs from its first device operation to its last.  The
program's spans are put on the trace's clock by ``spans.trace_origin``."""

from bench import spans
from bench import trace as btrace

COVERED = ("snapshot", "fault", "inject")


def read(run):
    origin = spans.trace_origin(run)
    steps = run["counters"].get("traced_steps")
    if origin is None or not steps:
        return None
    events = run["trace"]["events"]
    lo = min(ev.start for ev in events)
    hi = max(ev.end for ev in events)
    covered = btrace.union([
        (spans.obs.to_trace_ns(r.start, origin),
         spans.obs.to_trace_ns(r.end, origin))
        for r in spans.in_window(run, COVERED)])
    idle = 0.0
    for g0, g1 in btrace.gaps(events, lo, hi):
        idle += g1 - g0 - sum(max(0.0, min(g1, c1) - max(g0, c0))
                              for c0, c1 in covered)
    return idle * 1e-6 / steps
