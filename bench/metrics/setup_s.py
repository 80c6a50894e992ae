"""Process start to the window's first due arrival: weights, engine,
compilation or cache loads, warm-up (host clock)."""


def read(run):
    return run.setup_s
