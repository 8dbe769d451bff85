"""Time the replay rung spends re-digesting its snapshot on the host, per
fault recovered in the window (the program's ``snapshot.verify`` span)."""

from bench import spans


def read(run):
    return spans.per_fault_ms(run, "snapshot.verify")
