"""Host milliseconds between two decode chunks, median: from the end of
the ``sched.wait`` that returned one chunk to the end of the
``sched.dispatch`` of the next, over pairs of consecutive quiet
boundaries (ones that ran a chunk and admitted, extended, evicted,
aborted and reset nothing).  Read from the program's own counter,
``stats["host"]["quiet_gap"]``, a log-spaced histogram over the whole
window and drain; the median is interpolated in log space inside its
bin.  Nothing to read where the program keeps no such counter."""
import math


def read(run):
    hist = (run.stats.get("host") or {}).get("quiet_gap")
    if not hist or not sum(hist["counts"]):
        return None
    edges, counts = hist["edges_s"], hist["counts"]
    half, seen = sum(counts) / 2.0, 0
    for i, c in enumerate(counts):
        if seen + c >= half and c:
            if i == 0:
                return 1e3 * edges[0]
            if i == len(edges):
                return 1e3 * edges[-1]
            lo, hi = math.log(edges[i - 1]), math.log(edges[i])
            return 1e3 * math.exp(lo + (hi - lo) * (half - seen) / c)
        seen += c
    return None
