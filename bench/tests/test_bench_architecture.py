"""A new architecture is added as files alone: a module under a name the
harness has never seen (``tiny.RECORDED_ARCH``, which records its calls),
a configuration that names it, its checks and a cell.  The real harness
runs the cell end to end through that module, and ``limits.py`` reaches
weights and reference through it too."""
import json
import sys
import time

import pytest

from bench import harness, limits
from bench.tests import tiny


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


def calls(root):
    return (root / "bench/references/tiny_recorded.calls").read_text().split()


def test_run_goes_through_the_module_the_configuration_names(root):
    r = harness.execute(root, "tiny-arch.closed", 2**32 + 9, 1.5, False,
                        time.perf_counter(), device_check=tiny.no_chip_check)
    assert r["correct"], r["checks"]
    assert r["checks"]["tokens_checked"]["value"] >= \
        tiny.LIMITS["min_tokens_checked"]
    got = calls(root)
    # build: the program's config, the sizes, the weights; then the check
    assert got[:3] == ["program_kwargs", "dims", "make_weights"]
    assert got[3:] and set(got[3:]) == {"logits.f32"}


def test_limits_reach_weights_and_control_through_the_module(
        root, monkeypatch, capsys):
    monkeypatch.setattr(limits, "ROOT", root)
    monkeypatch.setattr(harness, "device_info", tiny.no_chip_check)
    monkeypatch.setattr(sys, "path", list(sys.path))
    assert limits.main(["--workload", "tiny-arch.closed", "--seeds", "2",
                        "--control", "1", "--seconds", "1"]) == 0
    got = calls(root)
    # the first seed's weights at build, the second's by harness.reseed
    assert got.count("make_weights") == 2
    assert "logits.f32" in got and "logits.int8" in got
    summary = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert len(summary["program"]) == 2 and len(summary["control"]) == 1
