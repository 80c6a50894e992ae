"""Tokens committed per verify step of a live row: the scheduler's
``stats["acceptance_length"]`` (accepted tokens over verify steps)."""


def read(run):
    v = run.stats.get("acceptance_length")
    return None if not v else v
