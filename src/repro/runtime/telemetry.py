"""Host spans and counters of the serving loop.

``Telemetry.span(name, **args)`` is the one helper: it enters
``jax.profiler.TraceAnnotation(name, **args)``, so the span lands on the
profiler's host clock (the clock the device trace is aligned to) whenever
a profiler runs, and it adds the span's ``time.perf_counter()`` duration
and self time (duration less the spans nested in it) to per-name
counters.  With no profiler running a span builds no annotation and
costs two clock reads and the counter updates; there is no switch.

``Telemetry.boundary()`` is the span of one ``ContinuousScheduler``
boundary (``sched.boundary``).  It closes with the boundary's ``kind``
and keeps the scheduler's counters:

* ``kind``: ``turnover`` when the boundary admitted, extended, evicted,
  aborted or reset a row; else ``quiet`` when it ran a decode chunk; else
  ``idle`` (nothing resident, or nothing ready to run);
* ``quiet_gap``: host seconds from the end of the ``sched.wait`` that
  returned one chunk to the end of the ``sched.dispatch`` of the next,
  where both boundaries are quiet, as a fixed log-spaced histogram;
* ``turnover_host_s``: host seconds of turnover boundaries spent outside
  ``sched.wait``.

One ``Telemetry`` belongs to one scheduler (router replicas are threads,
each with its own) and its memory is bounded: counters per span name and
a histogram of fixed size.
"""
from __future__ import annotations

import bisect
import math
import time
from typing import Dict, Optional

import jax

_annotation = jax.profiler.TraceAnnotation
_clock = time.perf_counter


class LogHistogram:
    """Counts of positive seconds in log-spaced bins, ``PER_DECADE`` a
    decade from ``LO_S`` to ``HI_S``, with one bin below and one above."""

    LO_S, HI_S, PER_DECADE = 1e-6, 10.0, 40

    def __init__(self):
        n = round(self.PER_DECADE * math.log10(self.HI_S / self.LO_S))
        self.edges = [self.LO_S * 10 ** (i / self.PER_DECADE)
                      for i in range(n + 1)]
        self.counts = [0] * (n + 2)

    def add(self, x: float) -> None:
        self.counts[bisect.bisect_right(self.edges, x)] += 1


class Span:
    """One timed host span; ``start``/``end`` are ``perf_counter``
    seconds, ``child`` the seconds of the spans nested in it."""

    __slots__ = ("tel", "name", "ann", "parent", "start", "end", "child")

    def __init__(self, tel: "Telemetry", name: str, args: dict):
        self.tel, self.name, self.child = tel, name, 0.0
        # no profiler running: no annotation to build
        self.ann = _annotation(name, **args) if _annotation.is_enabled() \
            else None

    def __enter__(self) -> "Span":
        if self.ann is not None:
            self.ann.__enter__()
        tel = self.tel
        self.parent, tel._open = tel._open, self
        self.start = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.end = _clock()
        self._count(self.end - self.start)
        if self.ann is not None:
            self.ann.__exit__(*exc)

    def _count(self, dt: float) -> None:
        tel = self.tel
        tel._open = self.parent
        if self.parent is not None:
            self.parent.child += dt
        c = tel.spans.get(self.name)
        if c is None:
            c = tel.spans[self.name] = [0.0, 0.0, 0]
        c[0] += dt
        c[1] += dt - self.child
        c[2] += 1


class BoundarySpan(Span):
    """``sched.boundary``: the scheduler marks ``turnover`` and the ends
    of the chunk's ``sched.dispatch`` (``dispatched``) and ``sched.wait``
    (``returned``); closing it sets ``kind`` and keeps the counters."""

    __slots__ = ("turnover", "dispatched", "returned", "wait0")

    def __init__(self, tel: "Telemetry"):
        super().__init__(tel, "sched.boundary", {})
        self.turnover = False
        self.dispatched: Optional[float] = None
        self.returned: Optional[float] = None
        self.wait0 = tel.seconds("sched.wait")

    def _count(self, dt: float) -> None:
        super()._count(dt)
        tel = self.tel
        kind = "turnover" if self.turnover else \
            "quiet" if self.returned is not None else "idle"
        if self.ann is not None:
            self.ann.set_metadata(kind=kind)
        k = tel.kinds.setdefault(kind, [0.0, 0])
        k[0] += dt
        k[1] += 1
        if kind == "quiet":
            if tel._gap_from is not None:
                tel.quiet_gap.add(self.dispatched - tel._gap_from)
            tel._gap_from = self.returned
        else:
            tel._gap_from = None
        if kind == "turnover":
            tel.turnover_host_s += dt - (tel.seconds("sched.wait")
                                         - self.wait0)


class Telemetry:
    """Spans and counters of one scheduler; ``reset()`` starts a stream,
    ``snapshot()`` returns them as plain numbers."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.spans: Dict[str, list] = {}    # name -> [s, self s, count]
        self.kinds: Dict[str, list] = {}    # boundary kind -> [s, count]
        self.quiet_gap = LogHistogram()
        self.admitted = 0
        self.turnover_host_s = 0.0
        self._open: Optional[Span] = None   # innermost open span
        self._gap_from: Optional[float] = None

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def boundary(self) -> BoundarySpan:
        return BoundarySpan(self)

    def seconds(self, name: str) -> float:
        c = self.spans.get(name)
        return c[0] if c else 0.0

    def snapshot(self) -> dict:
        return {
            "spans": {n: {"s": s, "self_s": x, "n": k}
                      for n, (s, x, k) in self.spans.items()},
            "kinds": {n: {"s": s, "n": k}
                      for n, (s, k) in self.kinds.items()},
            "quiet_gap": {"edges_s": list(self.quiet_gap.edges),
                          "counts": list(self.quiet_gap.counts)},
            "admitted": self.admitted,
            "turnover_host_s": self.turnover_host_s,
        }
