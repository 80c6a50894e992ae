"""95th percentile over requests of (last-token stamp - first-token
stamp) / (tokens - 1) (host clock)."""
from bench import latency


def read(run):
    return latency.tpot_ms(run.requests, run.window_s, 95)
