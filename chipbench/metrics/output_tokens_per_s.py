"""Output tokens that reached the clients in the window, per second."""


def read(run):
    return run.tokens() / run.window_s
