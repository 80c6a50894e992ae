"""Host milliseconds per admitted request spent in turnover boundaries
outside ``sched.wait``: the program's ``stats["host"]["turnover_host_s"]``
(boundaries that admitted, extended, evicted, aborted or reset a row)
over ``stats["host"]["admitted"]``, over the whole window and drain.
Nothing to read where the program keeps no such counter."""


def read(run):
    host = run.stats.get("host")
    if not host or not host.get("admitted"):
        return None
    return 1e3 * host["turnover_host_s"] / host["admitted"]
