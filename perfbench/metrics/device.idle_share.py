"""Worst chip's share of the traced window in which no operation ran."""
from benchlib.trace import idle_share


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return idle_share(run.trace) * 100.0
