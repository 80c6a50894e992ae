"""Device milliseconds per decode step of the bank: the summed device time
of the decode-chunk programs (``chunk_scan``) in the trace over the steps
those chunks ran (each chunk of K steps counted from the host spans)."""
from bench import programs


def read(run):
    if not run.trace:
        return None
    dev = programs.device(run.trace)
    execs = programs.executions(dev, programs.DECODE)
    chunks = run.trace["chunks"]
    if not chunks or len(execs) != len(chunks):
        return None
    steps = sum(c["K"] for c in chunks)
    return 1e3 * programs.seconds(execs) / steps
