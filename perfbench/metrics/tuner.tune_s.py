"""Seconds the tuner took in set-up (host clock around ``tune()``)."""


def read(run):
    return run.tune_s
