"""Time of the snapshot's host-to-device upload, to the device's holding
it, per fault recovered in the window (the program's ``snapshot.upload``
span)."""

from bench import spans


def read(run):
    return spans.per_fault_ms(run, "snapshot.upload")
