"""The harness finds a configuration, a traffic mix and a metric that are
added as files and entries, with no edit to its code; and ``run.py``
refuses to run off the chip or without the program beside it."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def root(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def _add_cell(root):
    cfg = json.loads((root / "bench/configs/qwen2-0.5b.json").read_text())
    cfg["name"] = "added-model"
    (root / "bench/configs/added-model.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/decode-1.json").read_text())
    tr["block"] = 4
    (root / "bench/traffic/added-mix.json").write_text(json.dumps(tr))
    (root / "bench/metrics/added_metric.py").write_text(
        "def read(run):\n    return run.stats['n'] * 2\n")
    (root / "bench/metrics/silent_metric.py").write_text(
        "def read(run):\n    return None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "added-model", "source": "x",
                            "file": "bench/configs/added-model.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "added-model.added-mix",
                              "config": "added-model",
                              "traffic": "added-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"] += [
        {"name": "added_metric", "unit": "x", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "output_tok_s", "workloads": ["added-model.added-mix"]},
        {"name": "silent_metric", "unit": "x", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "output_tok_s"}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_added_files_are_found_by_name(root):
    _add_cell(root)
    spec, cell, centry = harness.find_cell(root, "added-model.added-mix")
    assert harness.load_json(root / centry["file"])["name"] == "added-model"
    assert harness.load_json(
        root / "bench/traffic" / f"{cell['traffic']}.json")["block"] == 4
    entries = harness.metric_entries(spec, cell, trace=True)
    names = [m["name"] for m in entries]
    assert "added_metric" in names and "silent_metric" in names
    run = SimpleNamespace(stats={"n": 21})
    got = harness.read_metrics(
        root, [m for m in entries if m["name"].endswith("_metric")], run)
    # a reader that finds nothing is left out of the line
    assert got == {"added_metric": {"value": 42.0, "unit": "x"}}


def test_metric_listed_for_other_cells_is_not_reported(root):
    _add_cell(root)
    spec, cell, _ = harness.find_cell(root, "qwen2-0.5b.decode-1")
    names = [m["name"] for m in harness.metric_entries(spec, cell, True)]
    assert "added_metric" not in names and "silent_metric" in names
    e2e = [m["name"] for m in harness.metric_entries(spec, cell, False)]
    assert "setup_s" in e2e and "tpot_p50_ms" in e2e
    assert not set(e2e) & set(names)


def test_every_entry_of_the_benchmark_has_its_files():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert (REPO / "bench/references" / f"{cfg['reference']}.py").exists()
    for w in spec["workloads"]:
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").exists()
        assert (REPO / "bench/checks" / f"{w['name']}.json").exists()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (REPO / "bench/metrics" / f"{m['name']}.py").exists()


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for(REPO / "bench", "TPU v99")
    assert harness.peaks_for(REPO / "bench", "TPU v5 lite")[
        "hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.decode-1",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(root):
    p = _run(root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
