"""Mean time of the snapshot's device-to-host copy of the state, per
snapshot in the window (the program's ``snapshot.copy`` span)."""

from bench import spans


def read(run):
    return spans.mean_ms(run, "snapshot.copy")
