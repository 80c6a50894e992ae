"""What a reader finds in a reduced trace (``tracing.reduce_xplane``):
the programs and kernels of the serving path, by the names the program
gives them, and the traced window.

A jitted program shows on the device's "XLA Modules" line as
``jit_<function name>``; the functions are the engine's named jit targets.
"""
from __future__ import annotations

from bench.tracing import busy_seconds, op_family

DECODE = ("jit_chunk_scan",)
PREFILL = ("jit_admit_paged", "jit_prefill_prompt", "jit_prefill_extend",
           "jit_insert_paged")
# the paged tree-verify Pallas kernel's custom call in the "XLA Ops" line
# (``paged_tree_attention.<n>``)
PAGED_TREE_ATTENTION = ("paged_tree_attention",)


def device(trace: dict):
    """The one chip's op and program lines (cells run on one chip), or
    None where the trace holds no TPU plane."""
    return next(iter(trace["devices"].values()), None)


def _matches(name: str, names) -> bool:
    return any(name == n or name.startswith(n + "(") or
               name.startswith(n + ".") for n in names)


def executions(dev, names) -> list:
    return [] if dev is None else \
        [e for e in dev["modules"] if _matches(e[0], names)]


def kernel_events(dev, names) -> list:
    """A kernel's events: ops whose family is the kernel's name."""
    return [] if dev is None else \
        [e for e in dev["ops"] if op_family(e[0]) in names]


def seconds(events) -> float:
    return sum(e[2] - e[1] for e in events) * 1e-9


def window_bounds(trace: dict):
    """The traced window on the trace's clock, in ns: from the start of
    the first ``bench.boundary`` span to the end of the last."""
    b = [h for h in trace["host"] if h[0] == "bench.boundary"]
    return min(h[1] for h in b), max(h[2] for h in b)


def busy_and_window(trace: dict):
    """(device busy seconds, window seconds) over the traced window; busy
    is None where the trace holds no TPU plane."""
    t0, t1 = window_bounds(trace)
    dev = device(trace)
    busy = None if dev is None else busy_seconds(dev, t0, t1)
    return busy, (t1 - t0) * 1e-9
