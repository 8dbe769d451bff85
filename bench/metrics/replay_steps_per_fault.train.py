"""Steps the recovery ladder replayed per fault it recovered in the
window (``RecoveryEvent.steps_replayed``)."""


def read(run):
    c = run["counters"]
    if not c.get("recovered"):
        return None
    return c["replayed_steps"] / c["recovered"]
