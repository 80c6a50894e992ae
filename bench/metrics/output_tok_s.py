"""Output tokens flushed to clients inside the window over the window's
seconds (host clock)."""
from bench import latency


def read(run):
    return latency.rate(run.tokens_in_window, run.window_s)
