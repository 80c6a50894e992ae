"""One run of one benchmark cell: set-up, measured window, drain, output
check, metrics.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in the file the entry names, its traffic in
``bench/traffic/<traffic>.json``, the limits its output check is held to
in ``bench/checks/<cell>.json``, the reference its configuration names in
``bench/references/<reference>.py`` and each metric's reader in
``bench/metrics/<metric>.py``.  Adding a cell or a metric adds files and
entries; nothing here changes.

The system under test is the program's serving path as users run it: a
``DecodeEngine`` with a Medusa draft strategy over a paged bfloat16 KV
pool, Pallas kernels on a TPU, wrapped in ``ContinuousScheduler`` with
chunked prefill.  The window drives ``ContinuousScheduler.boundary()``
from this loop.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import latency, programs, traffic as traffic_mod, weights
from bench.tracing import (CompileClock, EngineSpans, idle_gaps,
                           reduce_xplane, top_ops)

# the traced part of a --trace 1 window: the last TRACE_S seconds of it
TRACE_S = 4.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------
# finding things by name
# --------------------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: Path, name: str):
    """(spec, cell, config entry) for workload ``name``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return spec, cell, configs[cell["config"]]


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_entries(spec: dict, cell: dict, trace: bool) -> list:
    """The metrics a cell reports: end-to-end ones with ``--trace 0``,
    per-layer ones with ``--trace 1``; an entry with ``workloads`` only in
    the cells it lists."""
    pool = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in pool
            if "workloads" not in m or cell["name"] in m["workloads"]]


def read_metrics(root: Path, entries: list, run) -> dict:
    out = {}
    for m in entries:
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py")
        v = reader.read(run)
        # nothing to read (or a tail over an unfinished request): left out
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------
def dims_of(config: dict, traffic: dict, padded_vocab: int) -> dict:
    """The sizes the weights and the reference need, from a configuration
    file in the source's own key names."""
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    return {
        "d_model": d, "n_layers": config["num_hidden_layers"],
        "n_heads": H, "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim", d // H),
        "d_ff": config["intermediate_size"],
        "vocab": config["vocab_size"], "vocab_padded": padded_vocab,
        "qkv_bias": bool(config.get("attention_bias", False)),
        "tied": bool(config["tie_word_embeddings"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "rope_theta": float(config["rope_theta"]),
        "medusa_heads": config["serving"]["medusa_heads"],
        "dtype": config["torch_dtype"],
        "n_out_max": int(traffic["output"]["max"]),
    }


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    s = config["serving"]
    d = config["hidden_size"]
    return ModelConfig(
        name=config["name"], arch_type="dense", source=config["source"],
        num_layers=config["num_hidden_layers"], d_model=d,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim", d // config["num_attention_heads"]),
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        qkv_bias=bool(config.get("attention_bias", False)),
        rope_theta=float(config["rope_theta"]),
        rmsnorm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        medusa_heads=s["medusa_heads"], medusa_top_k=s["medusa_top_k"],
        dtype=config["torch_dtype"])


@dataclasses.dataclass
class System:
    """The system under test and the benchmark's weights it serves."""
    engine: object
    sched: object
    spans: EngineSpans
    params: object
    heads: object
    dims: dict


def build(config: dict, traffic: dict, seed: int) -> System:
    import jax

    from repro.core.speculative import tree as T
    from repro.core.speculative.medusa import init_medusa
    from repro.models.api import get_model
    from repro.runtime.engine import DecodeEngine, DecodeStrategy
    from repro.runtime.scheduler import ContinuousScheduler

    s = config["serving"]
    cfg = program_config(config)
    model = get_model(cfg)
    dims = dims_of(config, traffic, cfg.padded_vocab)
    params, heads = weights.build(dims, seed)
    weights.check_layout((params, heads), jax.eval_shape(
        lambda k: (model.init_params(k), init_medusa(cfg, k)),
        jax.random.PRNGKey(0)))
    tree = T.build_tree(T.default_accs(cfg.medusa_heads, cfg.medusa_top_k),
                        s["tree_width"])
    max_len = traffic_mod.max_context(traffic) + tree.max_depth
    eng = DecodeEngine(model, params, heads=heads,
                       strategy=DecodeStrategy.medusa(tree), max_len=max_len,
                       chunk=s["chunk"], paged=True,
                       page_size=s["page_size"], hcmp="inline",
                       tree_kernel="dense")
    sched = ContinuousScheduler(eng, batch=int(traffic["batch"]),
                                chunk=s["chunk"],
                                prefill_chunk=s["prefill_chunk"])
    spans = EngineSpans(eng, sched, sched.prefill_chunk)
    return System(eng, sched, spans, params, heads, dims)


def warm_up(sys_: System, seed: int) -> None:
    """Run every program shape the window uses, once: the bank's bootstrap
    prefill and admission at the prefill piece, extension pieces, the
    decode chunk at every length the scheduler's power-of-two schedule
    can pick, and the batched row reset."""
    from repro.runtime.scheduler import Request

    sched, C = sys_.sched, sys_.sched.prefill_chunk
    K = sched.chunk
    budgets, k = [], 1
    while k <= K:                   # n tokens -> first chunk of k = n - 1
        budgets.append(k + 1)
        k *= 2
    rng = np.random.default_rng([int(seed) % (1 << 64), 9])
    plen = 2 * C + 1 if C else 16   # admit + a whole piece + a 1-token tail
    sys_.spans.reset_stream()
    sched.start([])
    for i, n in enumerate(budgets):
        toks = rng.integers(0, sys_.dims["vocab"], plen, dtype=np.int32)
        sys_.spans.expect(-1 - i, plen)
        sched.submit(Request(req_id=-1 - i, tokens=toks, n_tokens=n,
                             arrival=sched.now()))
        while sched.has_work:
            sched.boundary()
    sched.finish()
    if not sys_.engine.sched_drained():
        raise RuntimeError("warm-up left pages reserved")


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Window:
    requests: list          # per-request records (see latency.py)
    tokens_in_window: int
    stats: dict
    compiles: int
    trace: Optional[dict]
    served: dict            # req index -> (prompt, served tokens)
    t_start: float          # host perf_counter second the window opened


def run_window(sys_: System, traffic: dict, seed: int, seconds: float,
               trace_dir: Optional[str], clock: CompileClock) -> Window:
    """Drive the scheduler for ``seconds``, then drain.  ``trace_dir``
    traces the window's last ``TRACE_S`` seconds."""
    import jax

    from repro.runtime.scheduler import Request

    sched, spans = sys_.sched, sys_.spans
    vocab = sys_.dims["vocab"]
    closed = traffic["loop"] == "closed"
    drain_s = float(traffic["drain_s"])
    recs, served = {}, {}

    if closed:
        stream = traffic_mod.closed_loop(traffic, seed, vocab)
        queue = []
    else:
        queue = traffic_mod.open_loop(traffic, seed, vocab, seconds)

    def record(req, arrival):
        recs[req.index] = {"index": req.index, "prompt_len": len(req.prompt),
                           "n_out": req.n_out, "arrival": arrival,
                           "t_admit": None, "first": None, "last": None,
                           "n_tokens": 0, "done": False}
        served[req.index] = (req.prompt, [])
        spans.expect(req.index, len(req.prompt))

    spans.reset_stream()
    reqs = []
    for r in queue:
        record(r, r.due)
        reqs.append(Request(req_id=r.index, tokens=r.prompt, n_tokens=r.n_out,
                            arrival=r.due))
    annotate = jax.profiler.TraceAnnotation
    t_host0 = time.perf_counter()
    sched.start(reqs)
    if closed:
        for _ in range(int(traffic.get("clients", 1))):
            r = next(stream)
            record(r, sched.now())
            sched.submit(Request(req_id=r.index, tokens=r.prompt,
                                 n_tokens=r.n_out, arrival=sched.now()))
    tokens_in_window = 0
    tracing, traced = False, False
    trace_from = max(0.0, seconds - TRACE_S)
    deadline = seconds + drain_s
    while True:
        now = sched.now()
        if trace_dir and not traced and now >= trace_from:
            jax.profiler.start_trace(trace_dir, profiler_options=_options())
            tracing = traced = spans.recording = True
        if not sched.has_work or now >= deadline:
            break                   # all served, or the drain cap is reached
        with annotate("bench.boundary"):
            rep = sched.boundary()
        t = sched.now()
        for rid, toks in rep.emitted.items():
            rec = recs[rid]
            if rec["first"] is None:
                rec["first"] = t
            rec["last"] = t
            rec["n_tokens"] += len(toks)
            served[rid][1].extend(toks)
            if t <= seconds:
                tokens_in_window += len(toks)
        for res in rep.finished:
            rec = recs[res.req_id]
            rec["done"], rec["t_admit"] = True, res.t_admit
            if closed and t < seconds:      # the client sends its next one
                r = next(stream)
                record(r, t)
                sched.submit(Request(req_id=r.index, tokens=r.prompt,
                                     n_tokens=r.n_out, arrival=t))
        if tracing and t >= seconds:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()        # writes the trace: not drain
            deadline += time.perf_counter() - t_stop
            tracing = spans.recording = False
        if rep.idle and rep.next_arrival is not None:
            wait = min(rep.next_arrival, seconds + drain_s) - sched.now()
            if wait > 0:
                with annotate("bench.wait_for_arrival"):
                    time.sleep(wait)
    if tracing:
        jax.profiler.stop_trace()
        spans.recording = False
    compiles = clock.count_since(t_host0)
    _, stats = sched.finish()
    trace = None
    if trace_dir:
        trace = reduce_xplane(trace_dir)
        trace["chunks"] = list(spans.chunks)
        trace["calls"] = dict(spans.calls)
        tree = sys_.engine.strategy.tree
        trace["tree_width"] = int(tree.width)
        trace["tree_mask_nnz"] = int(np.asarray(tree.mask).sum())
    return Window(list(recs.values()), tokens_in_window, stats, compiles,
                  trace, served, t_host0)


# --------------------------------------------------------------------------
# the output check
# --------------------------------------------------------------------------
def _options():
    """Profiler options of a traced run: host spans on, the Python call
    tracer off (it would trace every interpreter call of the host loop)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def sample_for_check(win: Window, n: int, seed: int) -> list:
    """Finished requests to compare: the one with the most served tokens,
    then others drawn from the seed."""
    done = sorted((r for r in win.requests if r["done"]),
                  key=lambda r: (-r["n_tokens"], r["index"]))
    if not done:
        return []
    rest = done[1:]
    rng = np.random.default_rng([int(seed) % (1 << 64), 5])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [done[0]] + [rest[int(i)] for i in sorted(pick)]


def logit_gaps(ref, params, dims, prompt, tokens, pad_to, mode="f32"):
    """Per served token: (the reference's best logit minus the logit of the
    token served, the best logit).  With another ``mode`` than ``"f32"``
    the token is the one that mode's logits put first."""
    ref_lg = ref.logits(params, dims, prompt, tokens, pad_to=pad_to)
    best = ref_lg.max(axis=-1)
    if mode == "f32":
        pick = np.asarray(tokens, np.int64)
    else:
        pick = ref.logits(params, dims, prompt, tokens, pad_to=pad_to,
                          mode=mode).argmax(axis=-1)
    return best - ref_lg[np.arange(len(pick)), pick], best


def bf16_ulp(x) -> np.ndarray:
    """Spacing of bfloat16 values (8 significant bits) at magnitude x."""
    ax = np.maximum(np.abs(np.asarray(x, np.float64)), np.finfo(np.float32).tiny)
    return np.exp2(np.floor(np.log2(ax)) - 7)


def gap_stats(gaps) -> dict:
    """The compared numbers over (gap, best) pairs of every compared
    token: the widest gap, the widest gap past one bfloat16 spacing of the
    best logit (a program whose logits are bfloat16, as configured, cannot
    order two logits closer than that), and the mean gap."""
    g = np.concatenate([a for a, _ in gaps]) if gaps else np.zeros((0,))
    b = np.concatenate([c for _, c in gaps]) if gaps else np.zeros((0,))
    past = g - bf16_ulp(b)
    return {"max_logit_gap": float(g.max()) if g.size else 0.0,
            "max_gap_past_bf16_ulp": float(max(past.max(), 0.0))
            if g.size else 0.0,
            "mean_logit_gap": float(g.mean()) if g.size else 0.0,
            "tokens": int(g.size)}


def check_outputs(win: Window, ref, params, dims, traffic, seed,
                  limits: dict, mode: str = "f32") -> dict:
    """The compared numbers, each with its limit.  ``limits`` (the cell's
    ``bench/checks/<cell>.json``) says which gap statistics the cell is
    held to, any of ``gap_stats``'s: ``max_logit_gap`` (the widest gap of
    a served token below the reference's best), ``max_gap_past_bf16_ulp``
    and ``mean_logit_gap``.  ``mode="int8"`` reads the control in the program's
    place: at each compared position, the token the int8 reference puts
    first."""
    sample = sample_for_check(win, int(traffic["check_requests"]), seed)
    pad_to = _pad(traffic_mod.max_context(traffic))
    stat = gap_stats([logit_gaps(ref, params, dims, *win.served[r["index"]],
                                 pad_to, mode=mode) for r in sample])
    checks = {k: {"value": stat[k], "limit": limits[k]} for k in
              ("max_logit_gap", "max_gap_past_bf16_ulp", "mean_logit_gap")
              if k in limits}
    short = sum(1 for r in win.requests
                if r["done"] and r["n_tokens"] != r["n_out"])
    checks["tokens_checked"] = {"value": stat["tokens"],
                                "limit": limits["min_tokens_checked"]}
    checks["wrong_length"] = {"value": short, "limit": 0}
    return checks


def passes(checks: dict) -> bool:
    """Every gap at or under its limit, enough tokens compared, and every
    finished request served exactly its budget."""
    gaps_ok = all(c["value"] <= c["limit"] for k, c in checks.items()
                  if k not in ("tokens_checked", "wrong_length"))
    return (gaps_ok and checks["tokens_checked"]["value"]
            >= checks["tokens_checked"]["limit"]
            and checks["wrong_length"]["value"] == 0)


def _pad(n: int) -> int:
    return -(-n // 128) * 128


# --------------------------------------------------------------------------
# a whole run
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: dict
    config: dict
    traffic: dict
    dims: dict
    peaks: dict
    window_s: float
    setup_s: float
    requests: list
    tokens_in_window: int
    stats: dict
    trace: Optional[dict]


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (device 0: "
                     f"{devs[0].platform if devs else None})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks_for(bench: Path, kind: str) -> dict:
    table = load_json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, t_process0: float, device_check=device_info,
            trace_dir: Optional[str] = None) -> dict:
    """One run; returns the result line as a dict (``print_result`` prints
    it).  ``device_check`` raises ``NoChip`` off the chip."""
    spec, cell, centry = find_cell(root, workload)
    bench = root / "bench"
    config = load_json(root / centry["file"])
    traffic = load_json(bench / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(bench / "checks" / f"{workload}.json")
    ref = load_module(bench / "references" / f"{config['reference']}.py")
    device = device_check(int(cell["chips"]))
    peaks = peaks_for(bench, device["kind"])

    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock().install()

    sys_ = build(config, traffic, seed)
    warm_up(sys_, seed)
    win = run_window(sys_, traffic, seed, seconds,
                     trace_dir if trace else None, clock)
    setup_s = win.t_start - t_process0
    print(f"[bench] compilations in the window: {win.compiles}",
          file=sys.stderr, flush=True)
    device["memory_peak_bytes"] = _memory_peak()
    params, heads, dims = sys_.params, sys_.heads, sys_.dims
    del sys_, heads
    gc.collect()
    checks = check_outputs(win, ref, params, dims, traffic, seed, limits)
    attempted = len(latency.arrived(win.requests, seconds))
    failed = sum(1 for r in latency.arrived(win.requests, seconds)
                 if not r["done"])
    run = Run(cell, config, traffic, dims, peaks, seconds, setup_s,
              win.requests, win.tokens_in_window, win.stats, win.trace)
    result = {"correct": passes(checks), "attempted": attempted,
              "failed": failed,
              "metrics": read_metrics(root, metric_entries(spec, cell, trace),
                                      run),
              "device": device}
    if trace and win.trace and programs.device(win.trace) is not None:
        dev = programs.device(win.trace)
        t0, t1 = programs.window_bounds(win.trace)
        device["busy_s"], device["window_s"] = \
            programs.busy_and_window(win.trace)
        result["breakdown"] = {
            "device_ops": top_ops(dev),
            "idle_gaps": idle_gaps(dev, win.trace["host"], t0, t1)}
    result["checks"] = checks
    return result


def _memory_peak() -> int:
    import jax
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in jax.local_devices() if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def print_result(result: dict) -> None:
    checks = result["checks"]
    for name, c in checks.items():
        print(f"[bench] check {name}: {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr)
    print(f"[bench] correct: {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
