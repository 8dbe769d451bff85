"""Time of the replay rung per step it replayed: the self time of the
program's ``recover.replay`` spans (less the snapshot's verify and
upload, their children), summed over the window, over the sum of their
``steps``.  The self time is the replay loop and the certification."""

from bench import spans


def read(run):
    rungs = spans.in_window(run, ("recover.replay",))
    steps = sum(r.attrs.get("steps", 0) for r in rungs or ())
    if not steps:
        return None
    kids = spans.in_window(run, ("snapshot.verify", "snapshot.upload"))
    return 1e3 * sum(spans.self_seconds(r, kids) for r in rungs) / steps
