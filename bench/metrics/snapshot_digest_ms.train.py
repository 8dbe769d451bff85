"""Mean time of the snapshot's host digests of its copy, per snapshot in
the window (the program's ``snapshot.digest`` span)."""

from bench import spans


def read(run):
    return spans.mean_ms(run, "snapshot.digest")
