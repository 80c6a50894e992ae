"""The traffic generator is a pure function of (traffic file, seed)."""
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as tm

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2**31 + 12345
# an open-loop mix: chat prompts log-normal around 512, Poisson arrivals
OPEN = {"loop": "open", "batch": 8, "rate": 4.0,
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.7,
                   "min": 160, "max": 2048},
        "output": {"dist": "loguniform", "min": 32, "max": 256},
        "drain_s": 60, "check_requests": 4}


def _load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _closed(seed, n):
    return list(itertools.islice(
        tm.closed_loop(_load("decode-1"), seed, 151936), n))


def test_closed_loop_same_seed_same_requests():
    a, b = _closed(BIG_SEED, 20), _closed(BIG_SEED, 20)
    assert [(r.n_out, r.prompt.tolist()) for r in a] == \
        [(r.n_out, r.prompt.tolist()) for r in b]


def test_closed_loop_seeds_share_sizes_not_order():
    t = _load("decode-1")
    a, b = _closed(7, t["block"]), _closed(BIG_SEED, t["block"])
    sizes = lambda rs: sorted((len(r.prompt), r.n_out) for r in rs)
    assert sizes(a) == sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])


def test_closed_loop_sizes_within_the_distribution():
    t = _load("decode-1")
    for r in _closed(3, 3 * t["block"]):
        assert t["prompt"]["min"] <= len(r.prompt) <= t["prompt"]["max"]
        assert t["output"]["min"] <= r.n_out <= t["output"]["max"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 151936


@pytest.mark.parametrize("seed", [0, 99, BIG_SEED, -5])
def test_open_loop_is_a_pure_function_of_the_seed(seed):
    t = OPEN
    a = tm.open_loop(t, seed, 32000, 20.0)
    b = tm.open_loop(t, seed, 32000, 20.0)
    assert [(r.due, r.n_out, r.prompt.tolist()) for r in a] == \
        [(r.due, r.n_out, r.prompt.tolist()) for r in b]
    assert a[0].due == 0.0
    assert all(0.0 <= r.due < 20.0 for r in a)
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))


def test_open_loop_seeds_share_sizes_and_gaps():
    t = OPEN
    a = tm.open_loop(t, 1, 32000, 20.0)
    b = tm.open_loop(t, 2, 32000, 20.0)
    n = round(t["rate"] * 20.0)
    grid = sorted(tm.size_grid(t, n))
    for reqs in (a, b):
        got = sorted((len(r.prompt), r.n_out) for r in reqs)
        assert set(got) <= set(grid) and len(got) >= n - 2
    assert [r.due for r in a] != [r.due for r in b]


def test_lognormal_grid_median_and_clip():
    t = OPEN
    grid = tm.size_grid(t, 101)
    prompts = sorted(p for p, _ in grid)
    assert prompts[50] == t["prompt"]["median"]
    assert prompts[0] >= t["prompt"]["min"]
    assert prompts[-1] <= t["prompt"]["max"]
