"""How far the tuner's predicted step time is from the window's, in % of
the measured time per step: |predicted / measured - 1|."""


def read(run):
    return abs(run.predicted_step_s / run.step_s - 1.0) * 100.0
