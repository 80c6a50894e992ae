"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of device-op intervals / traced window)."""
from bench import programs


def read(run):
    if not run.trace:
        return None
    busy, window = programs.busy_and_window(run.trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / window)
