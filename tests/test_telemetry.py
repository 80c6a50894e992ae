"""Host spans and counters of the serving loop (runtime/telemetry.py).

  * a span's self time is its duration less the spans nested in it;
  * ``ContinuousScheduler.start()`` resets the counters;
  * the quiet-gap histogram keeps a fixed size whatever it counts;
  * a scripted stream gives the expected boundary kinds, one quiet gap
    per pair of consecutive quiet boundaries, and counts admissions;
  * under a profiler the ``sched.*`` spans sit on the host plane, nested
    in ``sched.boundary``, carry their request and boundary kind, and
    ``sched.dispatch`` encloses the dispatch of the chunk program;
  * the decode chunk's device ops carry the draft/verify/commit scopes.
"""
import glob
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.speculative import tree as T
from repro.core.speculative.medusa import init_medusa
from repro.models.api import get_model
from repro.runtime import telemetry
from repro.runtime.engine import (BatchEngine, SpeculativeEngine,
                                  _eos_scalar)
from repro.runtime.scheduler import ContinuousScheduler, Request


def _fake_clock(monkeypatch, times):
    it = iter(times)
    monkeypatch.setattr(telemetry, "_clock", lambda: next(it))


def test_nested_self_time(monkeypatch):
    tel = telemetry.Telemetry()
    _fake_clock(monkeypatch, [0.0, 1.0, 4.0, 5.0, 5.5, 10.0])
    with tel.span("outer"):
        with tel.span("inner", req=3):
            pass
        with tel.span("inner"):
            pass
    snap = tel.snapshot()["spans"]
    assert snap["outer"] == {"s": 10.0, "self_s": 6.5, "n": 1}
    assert snap["inner"] == {"s": 3.5, "self_s": 3.5, "n": 2}


def test_start_resets_the_counters():
    sched = ContinuousScheduler(SimpleNamespace(chunk=4), batch=2)
    tel = sched.telemetry
    with tel.span("sched.flush"):
        pass
    tel.admitted, tel.turnover_host_s = 3, 1.0
    tel.quiet_gap.add(1e-3)
    sched.start([])
    snap = tel.snapshot()
    assert snap["spans"] == {} and snap["kinds"] == {}
    assert snap["admitted"] == 0 and snap["turnover_host_s"] == 0.0
    assert sum(snap["quiet_gap"]["counts"]) == 0


def test_histogram_is_bounded():
    h = telemetry.LogHistogram()
    n = len(h.counts)
    assert n == len(h.edges) + 1 == 282          # 40 a decade, 1 us-10 s
    for x in np.geomspace(1e-9, 1e3, 20000):
        h.add(float(x))
    assert len(h.counts) == n and sum(h.counts) == 20000
    assert h.counts[0] and h.counts[-1]        # below lo and above hi
    h2 = telemetry.LogHistogram()
    h2.add(2.5e-3)
    i = h2.counts.index(1)
    assert h2.edges[i - 1] <= 2.5e-3 < h2.edges[i]


def _setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, n, seed):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab_size), np.int32)


def _kinds(sched, n):
    """Run ``n`` boundaries; the kind each one closed with."""
    out = []
    for _ in range(n):
        before = {k: v[1] for k, v in sched.telemetry.kinds.items()}
        sched.boundary()
        after = {k: v[1] for k, v in sched.telemetry.kinds.items()}
        out += [k for k in after if after[k] != before.get(k, 0)]
    return out


def test_scripted_stream_gives_the_expected_kinds():
    cfg, model, params = _setup()
    # sequential decoding: one token a step, so the chunk count is known
    eng = BatchEngine(model, params, max_len=64, chunk=4, paged=True,
                      page_size=8)
    sched = ContinuousScheduler(eng, batch=1, prefill_chunk=16)
    # 20-token prompt: admitted in a 16-token piece, then a 4-token piece
    # that makes the row live; 17 tokens = the first + 4 chunks of 4
    sched.start([Request(req_id=0, tokens=_prompt(cfg, 20, 1),
                         n_tokens=17)])
    assert _kinds(sched, 7) == ["turnover",   # admit
                                "turnover",   # last piece, chunk 1
                                "quiet",      # chunk 2
                                "quiet",      # chunk 3
                                "turnover",   # chunk 4, evict
                                "turnover",   # the freed row's reset
                                "idle"]       # nothing resident
    host = sched.telemetry.snapshot()
    # one pair of consecutive quiet boundaries: one gap
    assert sum(host["quiet_gap"]["counts"]) == 1
    assert host["admitted"] == 1
    spans = host["spans"]
    assert spans["sched.dispatch"]["n"] == spans["sched.fetch"]["n"] == 4
    # the bootstrap prefill's first token, the done/rem sync of each
    # chunk, and the last piece's first token at its flush
    assert spans["sched.wait"]["n"] == 6
    # a request that has not arrived yet leaves the boundary idle
    sched.submit(Request(req_id=1, tokens=_prompt(cfg, 8, 2), n_tokens=4,
                         arrival=sched.now() + 1e3))
    assert _kinds(sched, 1) == ["idle"]
    # an abort landing is a turnover too
    sched.abort(1)
    assert _kinds(sched, 1) == ["turnover"]
    assert sched.request_state(1) == "CANCELLED"
    _, stats = sched.finish()
    assert stats["host"]["admitted"] == 1
    assert "queue_wait_p50_s" not in stats


def test_self_times_add_up_to_the_boundary():
    cfg, model, params = _setup()
    heads = init_medusa(cfg, jax.random.PRNGKey(7))
    spec = T.build_tree(T.default_accs(cfg.medusa_heads, cfg.medusa_top_k),
                        8)
    eng = SpeculativeEngine(model, heads, params, spec, max_len=96, chunk=4,
                            paged=True, page_size=8)
    sched = ContinuousScheduler(eng, batch=2, prefill_chunk=16)
    reqs = [Request(req_id=i, tokens=_prompt(cfg, 10 + 9 * i, i),
                    n_tokens=6 + 5 * i) for i in range(4)]
    _, stats = sched.serve(reqs)
    host = stats["host"]
    events = [e for e in sched.events if e[0] == "admit"]
    assert host["admitted"] == len(events) == 4
    spans = host["spans"]
    b = spans.pop("sched.boundary")
    assert b["n"] == sum(k["n"] for k in host["kinds"].values())
    assert sum(s["self_s"] for s in spans.values()) == \
        pytest.approx(b["s"] - b["self_s"])
    assert 0 < host["turnover_host_s"] <= host["kinds"]["turnover"]["s"]


def test_spans_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    cfg, model, params = _setup()
    eng = BatchEngine(model, params, max_len=64, chunk=4, paged=True,
                      page_size=8)
    sched = ContinuousScheduler(eng, batch=1, prefill_chunk=16)

    def stream():
        return [Request(req_id=i, tokens=_prompt(cfg, 20, i), n_tokens=9)
                for i in range(2)]

    sched.serve(stream())              # compiles outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sched.serve(stream())
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(path[-1])
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    ev = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
          for line in host.lines for e in line.events]
    bounds = [e for e in ev if e[0] == "sched.boundary"]
    phases = [e for e in ev if e[0].startswith("sched.")
              and e[0] != "sched.boundary"]
    assert bounds and phases
    assert all(e[3].get("kind") in ("quiet", "turnover", "idle")
               for e in bounds)
    for name, s, t, _ in phases:
        assert any(bs <= s and t <= bt for _, bs, bt, _ in bounds), name
    for e in phases:
        if e[0] in ("sched.admit", "sched.extend", "sched.evict"):
            assert e[3].get("req") in (0, 1), e
    assert {e[0] for e in phases} >= {"sched.admit", "sched.extend",
                                      "sched.dispatch", "sched.wait",
                                      "sched.fetch", "sched.unpack",
                                      "sched.flush", "sched.evict"}
    dispatch = [e for e in ev if e[0] == "sched.dispatch"]
    runs = [e for e in ev if e[0] == "PjitFunction(chunk_scan)"]
    assert runs
    for _, s, t, _ in runs:
        assert any(ds <= s and t <= dt for _, ds, dt, _ in dispatch)


def test_decode_chunk_ops_carry_the_step_phases():
    cfg, model, params = _setup()
    heads = init_medusa(cfg, jax.random.PRNGKey(7))
    spec = T.build_tree(T.default_accs(cfg.medusa_heads, cfg.medusa_top_k),
                        8)
    eng = SpeculativeEngine(model, heads, params, spec, max_len=64, chunk=2)
    row = eng.sched_prefill({"tokens": jnp.zeros((1, 8), jnp.int32)})
    text = eng._chunk_fn(2).lower(
        eng.params, eng.heads, eng.strategy, row, jnp.zeros((1,), bool),
        jnp.full((1,), 4, jnp.int32),
        jnp.asarray(_eos_scalar(None), jnp.int32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for phase in ("draft", "verify", "commit"):
        assert any(n.startswith("jit(chunk_scan)/") and f"/{phase}/" in n
                   for n in names), phase
