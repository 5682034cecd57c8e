"""Training tokens stepped over the whole window, per second of it."""


def read(run):
    if run.kind != "train" or run.window_s <= 0:
        return None
    return run.steps * run.tokens_per_step / run.window_s
