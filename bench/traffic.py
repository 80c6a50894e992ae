"""The one traffic generator: a traffic file's parameters and a seed in,
requests out.

Every seed draws the same multiset of request sizes (and, in an open
loop, of inter-arrival gaps): the sizes are the midpoint quantiles of the
file's distributions, paired by a fixed permutation, so two seeds do the
same work.  The seed sets the order and the token ids.  That keeps the
run-to-run spread a property of the system, not of the draw.

A traffic file is JSON:

    {"loop": "closed" | "open",
     "batch": slots in the scheduler's bank,
     "prompt": <length distribution>, "output": <length distribution>,
     "block": sizes per stratified block (closed loop),
     "rate": requests/s (open loop),
     "drain_s": seconds after the window for in-flight requests to finish,
     "check_requests": requests the output check samples}

A length distribution is ``{"dist": "loguniform", "min": a, "max": b}`` or
``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
(clipped to [min, max]).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

# pairs prompt and output quantiles the same way for every seed
_PAIRING_SEED = 0x5EED


@dataclasses.dataclass(frozen=True)
class Req:
    """One generated request: its prompt ids, its output budget, and (open
    loop) the second after the window's start at which it is due."""
    index: int
    prompt: np.ndarray
    n_out: int
    due: float = 0.0


def _quantile(dist: dict, u: float) -> int:
    kind = dist["dist"]
    lo, hi = float(dist["min"]), float(dist["max"])
    if kind == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        x = float(dist["median"]) * math.exp(
            float(dist["sigma"]) * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(round(min(max(x, lo), hi)))


def size_grid(traffic: dict, n: int) -> List[tuple]:
    """``n`` (prompt, output) pairs: midpoint quantiles of both
    distributions, paired by a fixed permutation (the same for every
    seed)."""
    us = [(i + 0.5) / n for i in range(n)]
    prompts = [_quantile(traffic["prompt"], u) for u in us]
    outs = [_quantile(traffic["output"], u) for u in us]
    perm = np.random.default_rng(_PAIRING_SEED).permutation(n)
    return [(prompts[i], outs[int(perm[i])]) for i in range(n)]


def max_context(traffic: dict) -> int:
    """Longest prompt plus longest output the file can draw."""
    return int(traffic["prompt"]["max"]) + int(traffic["output"]["max"])


def _rng(seed: int, stream: int) -> np.random.Generator:
    # seeds up to and past 2**31 (and negative ones) map onto one stream
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def _prompt(rng, length: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=length, dtype=np.int32)


def closed_loop(traffic: dict, seed: int, vocab: int) -> Iterator[Req]:
    """Endless request stream for closed-loop clients: blocks of
    ``traffic["block"]`` sizes, each block the whole size grid in an order
    drawn from the seed."""
    grid = size_grid(traffic, int(traffic["block"]))
    order_rng, tok_rng = _rng(seed, 1), _rng(seed, 2)
    i = 0
    while True:
        for j in order_rng.permutation(len(grid)):
            p, o = grid[int(j)]
            yield Req(i, _prompt(tok_rng, p, vocab), o)
            i += 1


def open_loop(traffic: dict, seed: int, vocab: int,
              seconds: float) -> List[Req]:
    """The requests due in a window of ``seconds``: ``round(rate *
    seconds)`` sizes and exponential gaps at midpoint quantiles, both in
    orders drawn from the seed; requests due after the window are
    dropped."""
    rate = float(traffic["rate"])
    n = max(1, int(round(rate * seconds)))
    grid = size_grid(traffic, n)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    order_rng, gap_rng, tok_rng = _rng(seed, 1), _rng(seed, 3), _rng(seed, 2)
    order = order_rng.permutation(n)
    due = np.cumsum(np.asarray(gaps)[gap_rng.permutation(n)])
    due -= due[0]                    # the first request opens the window
    out = []
    for i in range(n):
        if due[i] >= seconds:
            break
        p, o = grid[int(order[i])]
        out.append(Req(i, _prompt(tok_rng, p, vocab), o, float(due[i])))
    return out
