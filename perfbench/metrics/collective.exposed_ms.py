"""Per step, the time of collective operations during which no other
operation ran on the same chip, worst chip, in ms."""
from benchlib.trace import exposed_collective_s


def read(run):
    if run.trace is None:
        return None
    s = exposed_collective_s(run.trace)
    return None if s is None else s / run.steps * 1e3
