"""End-to-end arithmetic on per-request stamps (pure functions).

Each request record carries the second it was due (``arrival``: open loop,
its scheduled time; closed loop, when its client sent it), the second its
first token was flushed to the client (``first``), the second its last
token was (``last``), the tokens it received (``n_tokens``) and whether it
finished (``done``).  All seconds are on one host clock whose zero is the
window's start.

Tails are taken over every request that arrived in the window; one that
never finished counts as an infinite latency, so it can only raise a tail.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation) of all values; None for
    none.  Infinite values (unfinished requests) stay in the sample."""
    if not len(values):
        return None
    a = np.sort(np.asarray(values, np.float64))
    pos = (len(a) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or a[hi] == a[lo]:
        return float(a[lo])
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))


def arrived(requests: Iterable[dict], window_s: float) -> List[dict]:
    return [r for r in requests if r["arrival"] < window_s]


def ttft_s(r: dict) -> float:
    if not r["done"] or r["first"] is None:
        return math.inf
    return r["first"] - r["arrival"]


def tpot_s(r: dict) -> Optional[float]:
    """Seconds per output token after the first; None for a one-token
    request (it has no gap), infinite for an unfinished one."""
    if not r["done"]:
        return math.inf
    if r["n_tokens"] < 2:
        return None
    return (r["last"] - r["first"]) / (r["n_tokens"] - 1)


def ttft_ms(requests, window_s, q):
    return _ms(percentile([ttft_s(r) for r in arrived(requests, window_s)],
                          q))


def tpot_ms(requests, window_s, q):
    vals = [tpot_s(r) for r in arrived(requests, window_s)]
    return _ms(percentile([v for v in vals if v is not None], q))


def queue_wait_ms(requests, window_s, q):
    vals = [(r["t_admit"] - r["arrival"]) if r["t_admit"] is not None
            else math.inf for r in arrived(requests, window_s)]
    return _ms(percentile(vals, q))


def rate(tokens_in_window: int, window_s: float) -> float:
    """Output tokens flushed inside the window over the window's length."""
    return tokens_in_window / window_s


def _ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else x * 1e3
