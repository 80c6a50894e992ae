"""Device milliseconds of prompt processing per admitted request: the
summed device time of the admission, prefill, insert and extend programs
in the trace over the requests admitted while it ran."""
from bench import programs


def read(run):
    if not run.trace:
        return None
    calls = run.trace["calls"]
    admitted = calls.get("sched_admit", 0) + calls.get("sched_prefill", 0)
    if not admitted:
        return None
    execs = programs.executions(programs.device(run.trace), programs.PREFILL)
    return 1e3 * programs.seconds(execs) / admitted
