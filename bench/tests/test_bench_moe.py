"""The sparse-expert, windowed architecture (``references/moe_swa_gqa.py``)
end to end through the real harness at a tiny size, its two readers on
synthetic inputs, and two planted faults the output check must catch:
the window ignored on the sliding layers, and the capacity drops of the
training dispatch put back into serving."""
import json
import time

import numpy as np
import pytest

from bench import harness, moe_shapes
from bench.tests import tiny

S, F = "sliding_attention", "full_attention"
# 4 layers (S S S F), window 8, 8 experts of which 2 a token; prompts of
# 40-120 tokens over pieces of 64, so the window binds and the first
# piece's routing would overflow a training capacity
CONFIG = {
    "name": "tiny-moe", "source": "test", "reference": "moe_swa_gqa",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 4, "layer_types": [S, S, S, F],
    "mlp_layer_types": ["sparse"] * 4, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "sliding_window": 8,
    "tie_word_embeddings": False, "attention_bias": False,
    "torch_dtype": "float32",
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 4, "original_max_position_embeddings":
                           512, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "serving": {"medusa_heads": 2, "medusa_top_k": 3, "tree_width": 4,
                "chunk": 4, "prefill_chunk": 64, "page_size": 8}}
TRAFFIC = {"loop": "closed", "clients": 1, "batch": 1,
           "prompt": {"dist": "loguniform", "min": 40, "max": 120},
           "output": {"dist": "loguniform", "min": 12, "max": 24},
           "block": 8, "drain_s": 20, "check_requests": 16}
# In float32, so that the program and the reference differ only in
# summation order.  In bfloat16 a routing near tie flips an expert between
# the program and the float32 reference about once in 60 tokens here (2
# of 8 experts a token), each flip moving a token's logits by up to ~1:
# over the 52-300 tokens a 1.5 s window checks (fewer on a loaded CPU)
# sound runs then read mean gaps of 0.015-0.030, the capacity drops
# 0.020-0.045, and the two overlap.  The chip cell holds the bfloat16
# path to its own limit.  Mean gap in float32, 3 seeds at windows of 0.2
# and 1.5 s on the CPU (52-304 tokens): sound runs 0 (every served token
# the reference's best), capacity drops put back 0.062-0.146, the window
# ignored 2.42-3.03.
LIMITS = {"mean_logit_gap": 0.02, "min_tokens_checked": 40}
CELL = "tiny-moe.closed"


@pytest.fixture
def root(tmp_path):
    root = tiny.make_root(tmp_path)
    (root / "bench/configs/tiny-moe.json").write_text(json.dumps(CONFIG))
    (root / "bench/traffic/tiny-moe-closed.json").write_text(
        json.dumps(TRAFFIC))
    (root / f"bench/checks/{CELL}.json").write_text(json.dumps(LIMITS))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-moe", "source": "test",
                            "file": "bench/configs/tiny-moe.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-moe",
                              "traffic": "tiny-moe-closed", "chips": 1,
                              "why": "test"})
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root, trace=False):
    return harness.execute(root, CELL, 2**33 + 5, 1.5, trace,
                           time.perf_counter(),
                           device_check=tiny.no_chip_check)


def test_cell_runs_end_to_end_through_the_module(root):
    r = run(root)
    assert r["correct"], r["checks"]
    assert r["checks"]["tokens_checked"]["value"] >= \
        LIMITS["min_tokens_checked"]


def test_experts_touched_read_from_the_program(root):
    r = harness.execute(root, CELL, 7, 1.5, True, time.perf_counter(),
                        device_check=tiny.no_chip_check)
    # no TPU plane on the CPU: trace readers are silent, counters are not
    touched = r["metrics"]["moe_experts_touched"]["value"]
    # 4-token trees x 2 picks over 8 experts: between 2 and 8 a layer
    assert 2.0 <= touched <= 8.0
    assert "moe_gmm_roofline" not in r["metrics"]


def test_window_ignored_on_the_sliding_layers(root, monkeypatch):
    from repro.models import transformer
    real = transformer._kind_args

    def no_window(kinds, window):
        _, rope = real(kinds, window)
        return 0, rope

    monkeypatch.setattr(transformer, "_kind_args", no_window)
    r = run(root)
    assert not r["correct"]
    assert r["checks"]["mean_logit_gap"]["value"] > LIMITS["mean_logit_gap"]


def test_capacity_drops_put_back(root, monkeypatch):
    from repro.models import mlp, transformer

    def capacity(cfg, lp, h, stacks):
        p = dict(lp["moe"], **{k: w[lp["moe"]["layer"]]
                               for k, w in stacks.items()})
        return mlp.moe_apply(cfg, p, h)[0], None

    monkeypatch.setattr(transformer, "_serve_mix", capacity)
    r = run(root)
    assert not r["correct"]


# --------------------------------------------------------------------------
# readers on synthetic inputs
# --------------------------------------------------------------------------
DIMS = {"n_layers": 8, "d_model": 2304, "d_expert": 896}
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
STATS = {"moe": {"layers": 8,
                 "decode": {"steps": 100, "picks": 100 * 8 * 128,
                            "experts_touched": 100 * 8 * 56},
                 "prefill": {"steps": 0, "picks": 0, "experts_touched": 0}}}


class FakeRun:
    def __init__(self, stats=STATS, trace=None):
        self.stats, self.trace = stats, trace
        self.dims, self.peaks = DIMS, PEAKS


def _reader(name):
    return harness.load_module(tiny.REPO / "bench/metrics" / f"{name}.py")


def test_experts_touched_reader():
    assert _reader("moe_experts_touched").read(FakeRun()) == 56.0
    assert _reader("moe_experts_touched").read(FakeRun(stats={})) is None


def _trace(n_events, ns_each):
    """Two decode chunks holding ``n_events`` gmm events between them, and
    a prefill piece's gmm event outside them.  As on the chip, an op
    event names no program: the program executions are their own line."""
    ops = [("gmm.3", i * 10 ** 9, i * 10 ** 9 + ns_each, "")
           for i in range(n_events)]
    ops.append(("gmm.9", -2 * 10 ** 9, -10 ** 9, ""))
    half = n_events // 2 * 10 ** 9
    modules = [("jit_chunk_scan(7)", 0, half, ""),
               ("jit_chunk_scan(7)", half, n_events * 10 ** 9, ""),
               ("jit_prefill_extend(2)", -2 * 10 ** 9, -10 ** 9, "")]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [], "chunks": [{"K": 8}, {"K": 4}]}


def test_gmm_roofline_reader():
    read = _reader("moe_gmm_roofline").read
    # 8 layers x 12 steps x 3 calls, each as long as its least time
    calls = moe_shapes.expert_matmuls(DIMS, 128.0, 56.0)
    least = [max(f / PEAKS["flops_bf16"], b / PEAKS["hbm_bytes_per_s"])
             for f, b in calls]
    per_call_ns = sum(least) / 3 * 1e9
    tr = _trace(8 * 12 * 3, per_call_ns)
    assert read(FakeRun(trace=tr)) == pytest.approx(100.0, rel=1e-9)
    # each call reads 56 experts' weights: memory-bound
    f, b = calls[0]
    assert b / PEAKS["hbm_bytes_per_s"] > f / PEAKS["flops_bf16"]
    assert b == pytest.approx(56 * 2304 * 896 * 2 + 128 * (2304 + 896) * 2)
    # the wrong number of events: the kernel was not found
    assert read(FakeRun(trace=_trace(8 * 12 * 3 - 1, 1.0))) is None
    assert read(FakeRun(stats={}, trace=tr)) is None
