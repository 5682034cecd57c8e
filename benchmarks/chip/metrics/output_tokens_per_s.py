"""Output tokens the host held over the whole window, per second of it (the
window ends with the last engine step that began before the close)."""


def read(run):
    if run.kind != "serve" or run.window_s <= 0:
        return None
    return run.tokens_in_window / run.window_s
