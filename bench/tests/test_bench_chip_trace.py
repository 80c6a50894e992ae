"""The reduction from trace to metrics on a small trace recorded on a TPU
v5e: qwen2-0.5b at full width, one 300-token request admitted in three
pieces, then one decode chunk of 4 steps (``data/`` holds the gzipped
xplane and the host's counts for it)."""
import gzip
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, programs, tracing

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    with gzip.open(DATA / "qwen2-0.5b.small.xplane.pb.gz", "rb") as f, \
            open(d / "small.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    tr = tracing.reduce_xplane(str(d.parents[2]))
    host = json.loads((DATA / "qwen2-0.5b.small.host.json").read_text())
    for c in host["chunks"]:
        c["ns"], c["ctx0"] = np.asarray(c["ns"]), np.asarray(c["ctx0"])
    tr.update(chunks=host["chunks"], calls=host["calls"],
              tree_width=host["tree_width"],
              tree_mask_nnz=host["tree_mask_nnz"])
    return tr, host["dims"]


def read(name, tr, dims):
    run = SimpleNamespace(trace=tr, dims=dims, peaks=PEAKS,
                          stats={"acceptance_length": 1.0})
    return harness.load_module(METRICS / f"{name}.py").read(run)


def test_one_tpu_plane_with_the_serving_programs(trace):
    tr, _ = trace
    assert list(tr["devices"]) == ["/device:TPU:0"]
    dev = programs.device(tr)
    assert len(programs.executions(dev, programs.DECODE)) == 1
    assert len(programs.executions(dev, programs.PREFILL)) >= 3
    assert any(h[0] == "bench.sched_step" for h in tr["host"])


def test_kernel_found_once_per_layer_and_step(trace):
    tr, dims = trace
    ev = programs.kernel_events(programs.device(tr),
                                programs.PAGED_TREE_ATTENTION)
    assert len(ev) == dims["n_layers"] * 4


def test_busy_idle_and_breakdown(trace):
    tr, _ = trace
    busy, window = programs.busy_and_window(tr)
    assert 0 < busy < window
    t0, t1 = programs.window_bounds(tr)
    gaps = tracing.idle_gaps(programs.device(tr), tr["host"], t0, t1)
    assert gaps and all(name.startswith(("bench.", "no bench span"))
                        for name, _ in gaps)
    ops = tracing.top_ops(programs.device(tr))
    assert ops and not any(k.split("/")[-1] in tracing.CONTAINERS
                           for k, _ in ops)
    assert sum(v for _, v in ops) <= busy * 1.0001


def test_metrics_in_range(trace):
    tr, dims = trace
    step = read("decode_step_ms", tr, dims)
    assert 0.5 < step < 100
    assert read("prefill_ms", tr, dims) > 0
    roof = read("paged_tree_attention_roofline", tr, dims)
    assert 0 < roof <= 100
    mfu = read("step_mfu", tr, dims)
    assert 0 < mfu <= 100
    assert 0 <= read("device_idle_pct", tr, dims) < 100
