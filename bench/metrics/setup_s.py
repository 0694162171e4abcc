"""Seconds from process start to the first measured instant: data,
calibration, warm-up and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
