"""The reduction from trace events to metrics, on synthetic events: busy
union, idle gaps named by host spans, program and kernel time by name,
and the roofline and MFU arithmetic of the readers."""
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, programs, shapes, tracing

METRICS = Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000          # ns

DIMS = {"d_model": 896, "n_layers": 2, "n_heads": 14, "n_kv_heads": 2,
        "head_dim": 64, "d_ff": 4864, "vocab": 151936}
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return harness.load_module(METRICS / f"{name}.py").read


def test_union_merges_overlaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert tracing.union_seconds(iv) == pytest.approx(30e-9)


def test_busy_is_clipped_to_the_window():
    dev = {"ops": [("a", 0, 10 * MS, ""), ("b", 15 * MS, 30 * MS, "")]}
    assert tracing.busy_seconds(dev, 5 * MS, 20 * MS) == pytest.approx(
        0.010)


def test_idle_gaps_named_by_the_innermost_host_span():
    dev = {"ops": [("op", 0, 10 * MS, ""), ("op", 20 * MS, 30 * MS, "")]}
    host = [("bench.boundary", 0, 40 * MS, {}),
            ("bench.sched_extend", 12 * MS, 18 * MS, {})]
    gaps = tracing.idle_gaps(dev, host, 0, 40 * MS)
    assert gaps[0] == ["bench.sched_extend", pytest.approx(0.010)]
    assert gaps[1] == ["bench.boundary", pytest.approx(0.010)]


def test_op_names_from_hlo_text():
    text = ("%paged_tree_attention.6 = bf16[1,2,112,64]{3,2,1,0} "
            "custom-call(s32[1,57]{1,0} %compare_select_fusion.30)")
    assert tracing.op_name(text) == "paged_tree_attention.6"
    assert tracing.op_family("paged_tree_attention.6") == \
        "paged_tree_attention"
    assert tracing.op_family("sort") == "sort"


def test_top_ops_leave_out_enclosing_loops():
    dev = {"ops": [("while.137", 0, 10 * MS, "jit_chunk_scan(1)"),
                   ("sort.12", 1 * MS, 3 * MS, "jit_chunk_scan(1)")]}
    assert tracing.top_ops(dev) == [["jit_chunk_scan/sort",
                                     pytest.approx(0.002)]]


def test_top_ops_groups_by_program_and_name():
    dev = {"ops": [("fusion.1", 0, 2 * MS, "jit_chunk_scan"),
                   ("fusion.1", 3 * MS, 4 * MS, "jit_chunk_scan"),
                   ("copy", 5 * MS, 9 * MS, "jit_admit_paged")]}
    assert tracing.top_ops(dev) == [
        ["jit_admit_paged/copy", pytest.approx(0.004)],
        ["jit_chunk_scan/fusion", pytest.approx(0.003)]]


def _trace(n_steps=8, ctx=500, kernel_us=20.0, with_kernel=True):
    """A reduced trace of one decode chunk of ``n_steps`` steps, B=1."""
    L = DIMS["n_layers"]
    ops, t = [], 0
    for _ in range(n_steps * L):
        if with_kernel:
            ops.append(("paged_tree_attention.3", t, t + int(kernel_us * 1e3),
                        "jit_chunk_scan"))
        ops.append(("fusion.9", t + 30_000, t + 100_000, "jit_chunk_scan"))
        t += 110_000
    modules = [("jit_chunk_scan(7)", 0, t, ""),
               ("jit_admit_paged(3)", t, t + 2 * MS, "")]
    ns = np.ones((n_steps, 1), np.int64)
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [("bench.boundary", 0, t + 4 * MS, {})],
            "chunks": [{"K": n_steps, "ns": ns,
                        "ctx0": np.asarray([ctx])}],
            "calls": {"sched_admit": 1, "sched_step": 1},
            "tree_width": 16, "tree_mask_nnz": 40}


def run_of(trace):
    return SimpleNamespace(trace=trace, dims=DIMS, peaks=PEAKS,
                           stats={"acceptance_length": 1.0})


def test_decode_step_and_prefill_from_program_time():
    tr = _trace()
    end = tr["devices"]["/device:TPU:0"]["modules"][0][2]
    assert reader("decode_step_ms")(run_of(tr)) == pytest.approx(
        end / 8 / MS)
    assert reader("prefill_ms")(run_of(tr)) == pytest.approx(2.0)


def test_decode_step_needs_one_program_per_chunk():
    tr = _trace()
    tr["chunks"].append(dict(tr["chunks"][0]))
    assert reader("decode_step_ms")(run_of(tr)) is None


def test_roofline_least_time_over_kernel_time():
    tr = _trace(n_steps=4, ctx=1000, kernel_us=10.0)
    got = reader("paged_tree_attention_roofline")(run_of(tr))
    least = 0.0
    for k in range(4):
        f, b = shapes.paged_tree_attention(DIMS, 16, 40, [1000 + k])
        least += max(f / PEAKS["flops_bf16"], b / PEAKS["hbm_bytes_per_s"])
    assert got == pytest.approx(100 * least / (4 * 10e-6))
    assert 0 < got < 100


def test_roofline_silent_when_the_kernel_is_not_found():
    tr = _trace(with_kernel=False)
    assert reader("paged_tree_attention_roofline")(run_of(tr)) is None


def test_step_mfu_counts_committed_tokens_at_their_context():
    tr = _trace(n_steps=8, ctx=100)
    _, window = programs.busy_and_window(tr)
    want = sum(shapes.token_flops(DIMS, 100 + k) for k in range(8))
    got = reader("step_mfu")(run_of(tr))
    assert got == pytest.approx(100 * want / (window * PEAKS["flops_bf16"]))


def test_idle_share_of_the_window():
    tr = _trace()
    busy, window = programs.busy_and_window(tr)
    assert reader("device_idle_pct")(run_of(tr)) == pytest.approx(
        100 * (1 - busy / window))
    assert 0 < busy < window


def test_untraced_run_reads_nothing():
    for name in ("decode_step_ms", "prefill_ms", "device_idle_pct",
                 "step_mfu", "paged_tree_attention_roofline"):
        assert reader(name)(run_of(None)) is None


def test_bf16_spacing_and_gap_statistics():
    assert harness.bf16_ulp([3.0, 2.0, 5.0, -3.0]).tolist() == \
        [2 ** -6, 2 ** -6, 2 ** -5, 2 ** -6]
    gaps = [(np.asarray([0.0, 0.01, 0.05]), np.asarray([3.0, 3.0, 3.0]))]
    st = harness.gap_stats(gaps)
    assert st["max_logit_gap"] == pytest.approx(0.05)
    assert st["max_gap_past_bf16_ulp"] == pytest.approx(0.05 - 2 ** -6)
    assert st["mean_logit_gap"] == pytest.approx(0.02)
    assert st["tokens"] == 3
    within = harness.gap_stats([(np.asarray([0.01]), np.asarray([3.0]))])
    assert within["max_gap_past_bf16_ulp"] == 0.0


def test_shapes_count_cached_kv_once():
    f, b = shapes.paged_tree_attention(DIMS, 16, 40, [0])
    f2, b2 = shapes.paged_tree_attention(DIMS, 16, 40, [100])
    assert b2 - b == 2 * 64 * 2 * 2 * 100          # K and V, bf16, 2 heads
    assert f2 - f == 4 * 64 * 14 * 16 * 100
    assert shapes.paged_tree_attention(DIMS, 16, 40, []) == (0, 0)
