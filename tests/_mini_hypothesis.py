"""Deterministic stand-in for the subset of `hypothesis` this suite uses.

The property tests import it directly, so every machine replays the same
examples: it draws a fixed number of seeded pseudo-random examples per
test (deterministic across runs).  API surface:
``given``, ``settings``, and ``strategies.{integers,floats,sampled_from,
tuples}`` with ``.map``.  Shrinking/reporting are intentionally absent — on
failure the raw example values appear in the assertion traceback.
"""
from __future__ import annotations

import functools
import inspect
import zlib

import numpy as np


class _Strategy:
    def __init__(self, draw):
        self._draw = draw

    def sample(self, rng):
        return self._draw(rng)

    def map(self, fn):
        return _Strategy(lambda rng: fn(self._draw(rng)))


class strategies:                                   # noqa: N801 (mimic module)
    @staticmethod
    def integers(min_value, max_value):
        return _Strategy(lambda rng: int(rng.integers(min_value,
                                                      max_value + 1)))

    @staticmethod
    def floats(min_value, max_value):
        return _Strategy(lambda rng: float(rng.uniform(min_value, max_value)))

    @staticmethod
    def sampled_from(seq):
        items = list(seq)
        return _Strategy(lambda rng: items[int(rng.integers(len(items)))])

    @staticmethod
    def tuples(*strats):
        return _Strategy(lambda rng: tuple(s.sample(rng) for s in strats))


def settings(max_examples=20, deadline=None, **_ignored):
    def deco(fn):
        fn._mini_max_examples = max_examples
        return fn
    return deco


def given(**strats):
    def deco(fn):
        n_default = getattr(fn, "_mini_max_examples", 20)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            seed = zlib.crc32(fn.__qualname__.encode())
            rng = np.random.default_rng(seed)
            for _ in range(n_default):
                drawn = {k: s.sample(rng) for k, s in strats.items()}
                fn(*args, **kwargs, **drawn)

        # hide the drawn params from pytest's fixture resolution
        sig = inspect.signature(fn)
        wrapper.__signature__ = sig.replace(parameters=[
            p for name, p in sig.parameters.items() if name not in strats])
        return wrapper
    return deco
