"""Time the replay rung spends digesting the uploaded snapshot on the
device against its stored digests, per fault recovered in the window
(the program's ``snapshot.verify`` span)."""

from bench import spans


def read(run):
    return spans.per_fault_ms(run, "snapshot.verify")
