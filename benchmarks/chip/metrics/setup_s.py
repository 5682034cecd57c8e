"""Set-up: process start to the window's start (imports, weights, compile or
cache loads, warm-up, and for training the three checked steps)."""


def read(run):
    return run.setup_s
