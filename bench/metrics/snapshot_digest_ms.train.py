"""Mean time of the snapshot's device digest of the live state, per
snapshot in the window (the program's ``snapshot.digest`` span: leaf
groups packed and digested on the device, one fetch of the table)."""

from bench import spans


def read(run):
    return spans.mean_ms(run, "snapshot.digest")
