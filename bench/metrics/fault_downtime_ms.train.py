"""Mean downtime of a fault in the window: the program's ``fault`` span,
from the report's arrival through the ladder, the canary refresh and the
parity rebuild to the state the loop resumes from being on the device."""

from bench import spans


def read(run):
    return spans.mean_ms(run, "fault")
