"""The scheduler-layer metrics, read from the program's own counters
(``stats["host"]``): the readers on made-up counters, on a program that
keeps none, against the benchmark's ``EngineSpans`` counts, and in one
traced run of a tiny cell on the CPU."""
import math
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny
from bench.tracing import EngineSpans

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def read(name, stats):
    run = SimpleNamespace(stats=stats, trace=None)
    return harness.load_module(METRICS / f"{name}.py").read(run)


def host(gaps=(), admitted=0, turnover_host_s=0.0):
    from repro.runtime.telemetry import Telemetry
    tel = Telemetry()
    for g in gaps:
        tel.quiet_gap.add(g)
    tel.admitted, tel.turnover_host_s = admitted, turnover_host_s
    return tel.snapshot()


def test_host_gap_is_the_median_gap():
    # log-spaced bins 40 a decade: the median is found within its bin
    gaps = [0.4e-3] * 10 + [0.8e-3] * 21 + [5e-3] * 10
    v = read("host_gap_ms", {"host": host(gaps)})
    assert v == pytest.approx(0.8, rel=10 ** (1 / 40) - 1)
    assert read("host_gap_ms", {"host": host([2.0e-3])}) == \
        pytest.approx(2.0, rel=0.06)
    # nothing counted: nothing to read
    assert read("host_gap_ms", {"host": host()}) is None


def test_admit_host_is_per_admitted_request():
    v = read("admit_host_ms", {"host": host(admitted=4,
                                            turnover_host_s=0.2)})
    assert v == pytest.approx(50.0)
    assert read("admit_host_ms", {"host": host()}) is None


def test_a_program_without_the_counters_reads_nothing():
    stats = {"acceptance_length": 1.0}
    assert read("host_gap_ms", stats) is None
    assert read("admit_host_ms", stats) is None


def test_counters_agree_with_the_engine_spans():
    import jax

    from repro.configs import get_config
    from repro.core.speculative import tree as T
    from repro.core.speculative.medusa import init_medusa
    from repro.models.api import get_model
    from repro.runtime.engine import SpeculativeEngine
    from repro.runtime.scheduler import ContinuousScheduler, Request

    cfg = get_config("qwen2-0.5b").reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    heads = init_medusa(cfg, jax.random.PRNGKey(7))
    spec = T.build_tree(T.default_accs(cfg.medusa_heads, cfg.medusa_top_k),
                        8)
    eng = SpeculativeEngine(model, heads, params, spec, max_len=96, chunk=4,
                            paged=True, page_size=8)
    sched = ContinuousScheduler(eng, batch=2, prefill_chunk=16)
    spans = EngineSpans(eng, sched, sched.prefill_chunk)
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(5):
        n = int(rng.integers(10, 40))
        spans.expect(i, n)
        reqs.append(Request(req_id=i, n_tokens=int(rng.integers(5, 20)),
                            tokens=rng.integers(0, cfg.vocab_size, n,
                                                dtype=np.int32)))
    spans.recording = True
    _, stats = sched.serve(reqs)
    h = stats["host"]["spans"]
    assert h["sched.dispatch"]["n"] == spans.calls["sched_step"]
    assert h["sched.unpack"]["n"] == spans.calls["sched_emitted"]
    assert stats["host"]["admitted"] == 5 == \
        sum(1 for e in sched.events if e[0] == "admit")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def test_traced_tiny_run_reads_both(root, tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    r = harness.execute(root, "tiny.closed", 2**33 + 11, 3.0, True,
                        time.perf_counter(), device_check=tiny.no_chip_check,
                        trace_dir=str(tmp_path / "trace"))
    assert r["correct"], r["checks"]
    for name in ("host_gap_ms", "admit_host_ms"):
        v = r["metrics"][name]
        assert v["unit"] == "ms" and math.isfinite(v["value"]) \
            and v["value"] > 0, name
