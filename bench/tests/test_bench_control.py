"""The control, the reference computed in int8 (one precision below the
configuration's bfloat16) and read in the program's place, comes out as
not correct under the cell's limits, where the program's own served
tokens pass them.  At a tiny size on the CPU; the full-size readings are
in PERF.md."""
import pytest

from bench import harness
from bench.tests import tiny
from bench.tracing import CompileClock


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("tiny"))
    _, cell, centry = harness.find_cell(root, "tiny.closed")
    config = harness.load_json(root / centry["file"])
    traffic = harness.load_json(root / "bench/traffic/tiny-closed.json")
    limits = harness.load_json(root / "bench/checks/tiny.closed.json")
    ref = harness.load_module(root / "bench/references/dense_gqa.py")
    sys_ = harness.build(config, traffic, 11)
    harness.warm_up(sys_, 11)
    out = []
    for seed in (1_000_003, 1_007_922, 1_015_841):
        p, h = harness.weights.build(sys_.dims, seed)
        sys_.engine.params, sys_.engine.heads = p, h
        win = harness.run_window(sys_, traffic, seed, 2.0, None,
                                 CompileClock())
        out.append((win, p, seed))
    return sys_.dims, traffic, limits, ref, out


def test_program_passes_and_control_fails(served):
    dims, traffic, limits, ref, runs = served
    for win, params, seed in runs:
        sound = harness.check_outputs(win, ref, params, dims, traffic, seed,
                                      limits)
        assert harness.passes(sound), sound
        control = harness.check_outputs(win, ref, params, dims, traffic,
                                        seed, limits, mode="int8")
        assert not harness.passes(control), control
        assert control["mean_logit_gap"]["value"] > \
            control["mean_logit_gap"]["limit"]
