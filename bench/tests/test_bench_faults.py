"""Whole runs on the CPU at a tiny size, with the look for a chip skipped:
a sound run is correct, and each fault a serving cell can have, planted in
the timed path, makes ``correct`` false."""
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # JAX read the variable at import: setting it now only keeps the
    # harness from turning a cache on in the repository
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")


def run(root, cell, seed=2**32 + 7, seconds=1.5):
    return harness.execute(root, cell, seed, seconds, False,
                           time.perf_counter(),
                           device_check=tiny.no_chip_check)


@pytest.mark.parametrize("cell", ["tiny.closed", "tiny.open"])
def test_sound_run_is_correct(root, cell):
    r = run(root, cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tpot_p50_ms", "tpot_p95_ms",
                                 "output_tok_s", "setup_s"}
    assert list(r)[-1] == "checks"


def test_token_altered_where_it_is_produced(root, monkeypatch):
    from repro.runtime.engine import DecodeEngine
    real = DecodeEngine.sched_emitted

    def altered(raw):
        out = real(raw)
        for row in out:
            if row:
                row[0] = (row[0] + 1) % tiny.CONFIG["vocab_size"]
        return out

    monkeypatch.setattr(DecodeEngine, "sched_emitted", staticmethod(altered))
    r = run(root, "tiny.closed")
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]


def test_step_that_leaves_the_cache_unchanged(root, monkeypatch):
    from repro.models import transformer
    monkeypatch.setattr(transformer, "commit",
                        lambda cfg, cache, *a, **kw: cache)
    r = run(root, "tiny.closed")
    assert not r["correct"]


def test_half_of_the_bank_left_out(root, monkeypatch):
    from repro.runtime.engine import DecodeEngine
    real = DecodeEngine.sched_emitted

    def half(raw):
        out = real(raw)
        return [row if b % 2 == 0 else [] for b, row in enumerate(out)]

    monkeypatch.setattr(DecodeEngine, "sched_emitted", staticmethod(half))
    r = run(root, "tiny.open")
    assert not r["correct"]
    assert r["checks"]["wrong_length"]["value"] > 0
