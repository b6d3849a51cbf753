"""Output tokens delivered to the host in the window, per second of it."""


def read(run):
    return run.tokens_out / run.window_s if run.tokens_out else None
