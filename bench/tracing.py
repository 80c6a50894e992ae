"""Spans and counters the benchmark records around the program, and the
reduction of a profiler trace to numbers.

* ``CompileClock`` counts JAX compilations (the ``/jax/core/compile/*``
  duration events) so a run can show that nothing compiled inside its
  window.
* ``EngineSpans`` wraps the engine's slot-protocol calls in
  ``jax.profiler.TraceAnnotation`` host spans (``bench.<call>``) and keeps
  the counts a reader needs: per decode chunk its steps and per-step
  accepted tokens, and the context each bank row held when the chunk
  started.
* ``reduce_xplane`` reads a trace with ``jax.profiler.ProfileData`` and
  returns the device ops, the executed programs and the host spans, all
  on the trace's clock.
"""
from __future__ import annotations

import glob
import time
from typing import Dict, List, Optional

import numpy as np


class CompileClock:
    """Compilations while installed: the ``/jax/core/compile/*`` duration
    events JAX reports, each kept as a (start, end) host span."""

    def __init__(self):
        self._spans: List[tuple] = []

    def __call__(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            end = time.perf_counter()
            self._spans.append((end - duration, end))

    def install(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def count_since(self, t0: float) -> int:
        """Compile events that ended after host second ``t0``."""
        return sum(1 for _, end in self._spans if end > t0)


class EngineSpans:
    """Host spans and counters around one engine's ``sched_*`` calls.

    Installed on the engine instance (the program is not edited): each
    wrapped call runs inside ``TraceAnnotation("bench.<name>")``.  While
    ``recording`` is on, every chunk appends ``{"K", "ns", "ctx0"}``: its
    steps, its (K, B) accepted tokens per step and row, and each row's
    cached context (tokens in the KV cache) when it started.  Row contexts
    follow the scheduler's admit/extend events and the accepted counts."""

    WRAPPED = ("sched_prefill", "sched_blank", "sched_insert", "sched_admit",
               "sched_extend", "sched_reset", "sched_step", "sched_emitted")

    def __init__(self, engine, sched, prefill_chunk: int):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.sched = sched
        self.C = prefill_chunk
        self.recording = False
        self.chunks: List[dict] = []
        self.calls: Dict[str, int] = {}
        self._ctx = np.zeros((sched.batch,), np.int64)
        self._pending = np.zeros((sched.batch,), np.int64)
        self._seen = 0
        self._step_K: Optional[int] = None
        self._prompt_len: Dict[int, int] = {}
        for name in self.WRAPPED:
            setattr(engine, name, self._wrap(name, getattr(engine, name)))

    def expect(self, req_id: int, prompt_len: int) -> None:
        """Tell the counters a request's prompt length before it is
        submitted (the scheduler's events carry only its id)."""
        self._prompt_len[req_id] = prompt_len

    def _wrap(self, name, fn):
        ann = self._annotation

        def call(*a, **kw):
            if name == "sched_step":
                self._follow_events()
                self._step_K = int(a[3])
            with ann(f"bench.{name}"):
                out = fn(*a, **kw)
            if self.recording:
                self.calls[name] = self.calls.get(name, 0) + 1
            if name == "sched_emitted":
                self._chunk_done(np.asarray(a[0][1]))
            return out

        return call

    def _follow_events(self) -> None:
        ev = self.sched.events
        for kind, rid, b in ev[self._seen:]:
            if kind == "admit":
                S = self._prompt_len.get(rid, 0)
                first = min(S, self.C) if self.C else S
                self._ctx[b] = first
                self._pending[b] = S - first
            elif kind == "extend":
                piece = min(self._pending[b], self.C)
                self._ctx[b] += piece
                self._pending[b] -= piece
        self._seen = len(ev)

    def _chunk_done(self, ns: np.ndarray) -> None:
        if self.recording and self._step_K is not None:
            self.chunks.append({"K": self._step_K, "ns": ns.copy(),
                                "ctx0": self._ctx.copy()})
        self._ctx += ns.sum(axis=0)
        self._step_K = None

    def reset_stream(self) -> None:
        """A new ``sched.start()`` clears the scheduler's event log."""
        self._seen = 0
        self._ctx[:] = 0
        self._pending[:] = 0


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------
def union_seconds(intervals) -> float:
    """Length of the union of (start_ns, end_ns) intervals, in seconds."""
    total, reach = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total * 1e-9


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def op_name(text: str) -> str:
    """An op's name from its event, which carries the HLO instruction
    (``%paged_tree_attention.6 = bf16[...] custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_family(name: str) -> str:
    """``fusion.224`` -> ``fusion``: ops of one kind, numbered apart."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


# control flow that encloses other ops on the same line
CONTAINERS = ("while", "conditional", "call")


def reduce_xplane(trace_dir: str) -> dict:
    """Device ops, executed programs and host spans of the newest trace
    under ``trace_dir``.

    Returns ``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]}``; each event is ``(name, start_ns, end_ns, extra)``
    where ``extra`` is the op's program (device ops) or the span's
    arguments (host spans)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                "Core" not in plane.name[len("/device:TPU:"):]:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [(op_name(e.name), e.start_ns, e.end_ns,
                                   _stat(e, "hlo_module") or "")
                                  for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns, e.end_ns, "")
                                      for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append((e.name, e.start_ns, e.end_ns,
                                            dict(e.stats)))
    return out


def busy_seconds(dev: dict, t0_ns: float, t1_ns: float) -> float:
    """Seconds in [t0, t1] in which some op ran on the device."""
    return union_seconds((max(s, t0_ns), min(e, t1_ns))
                         for _, s, e, _ in dev["ops"]
                         if e > t0_ns and s < t1_ns)


def idle_gaps(dev: dict, host: list, t0_ns: float, t1_ns: float,
              top: int = 10) -> list:
    """Device idle time in [t0, t1] grouped by the innermost ``bench.*``
    host span open at each gap's midpoint, longest first."""
    busy = sorted((s, e) for _, s, e, _ in dev["ops"])
    gaps, reach = [], t0_ns
    for s, e in busy:
        if s > reach:
            gaps.append((reach, min(s, t1_ns)))
        reach = max(reach, e)
    if reach < t1_ns:
        gaps.append((reach, t1_ns))
    spans = sorted(host, key=lambda h: h[1])
    by_name: Dict[str, float] = {}
    for gs, ge in gaps:
        if ge <= gs:
            continue
        mid = (gs + ge) / 2
        open_ = [h for h in spans if h[1] <= mid <= h[2]]
        name = min(open_, key=lambda h: h[2] - h[1])[0] if open_ \
            else "no bench span (host between calls)"
        by_name[name] = by_name.get(name, 0.0) + (ge - gs) * 1e-9
    return sorted(([k, v] for k, v in by_name.items()),
                  key=lambda kv: -kv[1])[:top]


def top_ops(dev: dict, top: int = 10) -> list:
    """Device seconds per (program, op family), most first; loops and
    other ops that enclose ops are left out (their time is their body's)."""
    tot: Dict[str, float] = {}
    for name, s, e, mod in dev["ops"]:
        fam = op_family(name)
        if fam in CONTAINERS:
            continue
        key = f"{mod.split('(')[0]}/{fam}" if mod else fam
        tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[
        :top]
