"""Serving launcher: batched Ghidorah speculative serving or batched
sequential serving on the local device(s), with the device-resident chunked
decode loop (one host sync per ``--chunk`` steps).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b-smoke \
      --mode ghidorah --width 8 --tokens 64 --batch 4 --chunk 8

Two serving shapes:

* default (``--arrivals none``): one fixed batch of ``--batch`` prompts is
  prefilled together and decoded to the token budget.  Throughput counts
  REAL emitted tokens (``stats["emitted_total"]``), not the EOS padding in
  the output buffer.
* replay (``--arrivals poisson --rate R --requests N``): N requests arrive
  as a rate-R Poisson process and flow through ``runtime/scheduler.py`` —
  ``--sched continuous`` admits/evicts per sequence at chunk boundaries
  (a freed cache row is immediately refilled from the queue),
  ``--sched static`` is the fixed-group baseline.  Reports aggregate
  tokens/sec plus per-request latency mean/p50/p95 (the tail is what the
  admission policies move — mean alone hides it).  ``--policy
  fifo|sjf|lpt`` picks the admission order (sjf/lpt may admit a small
  fundable request past a page-deferred head-of-line one;
  ``--age-limit N`` bounds their starvation by promoting a request
  deferred more than N boundaries to FIFO-head priority) and
  ``--prefill-chunk N`` admits prompts longer than N piecewise so one
  long prompt cannot stall the resident bank (attention families).

``--spec-width auto`` (ghidorah + continuous replay) switches ARCA from
the analytic SoC model to MEASURED profiling: the engine's compiled
per-width step functions are timed on this machine
(``arca.profile_engine``), ``choose_strategy`` picks the starting width
from measured tokens/sec, and the scheduler's adaptive mode keeps
re-deciding the width at chunk boundaries from the observed-acceptance
EMA (strategy switches are logged).

Capacity: the KV cache is sized so the full token budget fits
(prompt + tokens + tree depth of speculative overshoot).  An undersized
cache no longer wraps silently — the engines freeze a sequence at the
capacity boundary and ``n_emitted`` reports the shortfall.

``--paged`` swaps the dense per-row KV for the shared page pool
(runtime/cache.py): each sequence reserves only the pages its
prompt+budget needs, so ``--pool-pages`` bounds total KV memory instead of
``batch * max_len`` — shrink it below the dense equivalent to serve a
larger ``--batch`` at fixed memory (the sched_bench paged record measures
exactly this trade).  ``--kv-dtype int8`` quantizes the pool's pages with
per-page dequant scales (~3.5x fewer bytes/token — the same pool bytes
reserve more resident tokens); ``--tree-kernel sparse|auto`` splits the
paged verify into the quantized page walk + the block-masked tree kernel
(auto = ARCA measures both and picks per shape).

Fault-tolerant serving (``--replicas N``, ``--deadline-s``,
``--cancel-rate``, ``--inject-faults SEED``): the replay runs through the
async front end instead of in-process — N engine replicas behind
``runtime/router.py`` with retry+backoff, per-request deadlines, client
cancellations and (with ``--inject-faults``) the seeded chaos harness
(replica crash, chunk stalls, admission-time pool exhaustion).  The run
exits non-zero unless EVERY request reaches a typed terminal state and
every replica's page pool drains leak-free — the CI chaos smoke gate.
"""
from __future__ import annotations

import argparse
import asyncio
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import arca
from repro.core.speculative import tree as T
from repro.core.speculative.medusa import init_medusa
from repro.data.pipeline import MarkovDataset
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import get_model
from repro.runtime.engine import BatchEngine, SpeculativeEngine
from repro.runtime.faults import FaultPlan
from repro.runtime.router import ReplicaRouter, replay as router_replay
from repro.runtime.scheduler import (ContinuousScheduler, Request,
                                     poisson_arrivals, serve_static)
from repro.runtime.server import AsyncEngineServer
from repro.training import checkpoint


def _requests(args, data):
    prompts = data.sample(args.requests, args.prompt_len, seed=11)[:, :-1]
    arrivals = poisson_arrivals(args.requests, args.rate, seed=args.seed)
    return [Request(req_id=i, tokens=prompts[i].astype(np.int32),
                    n_tokens=args.tokens, arrival=float(arrivals[i]))
            for i in range(args.requests)]


def _once_then(prebuilt, build):
    """Engine factory that hands out the already-built engine first (its
    compiles are paid), then builds fresh replicas."""
    first = [prebuilt]

    def factory():
        if first:
            return first.pop()
        return build()
    return factory


def _fault_tolerant(args) -> bool:
    """Whether the replay must go through the async server/router plane."""
    return (args.replicas > 1 or args.deadline_s is not None
            or args.cancel_rate > 0 or args.inject_faults is not None)


def _replay_async(args, data, build_engine, adaptive=None):
    """Fault-tolerant replay: the arrival stream flows through N replica
    servers behind the router; with ``--inject-faults`` the seeded chaos
    plan crashes replica r0, stalls chunks and blocks admissions.  Exits
    non-zero unless every request is terminal and no replica leaked
    pages."""
    reqs = _requests(args, data)
    plan = None
    if args.inject_faults is not None:
        crash = {"r0": 6} if args.replicas > 1 else {}
        plan = FaultPlan(seed=args.inject_faults, crash=crash,
                         stall_rate=0.05, stall_s=0.01, exhaust_rate=0.05,
                         cancel_rate=args.cancel_rate)
    elif args.cancel_rate > 0:
        plan = FaultPlan(seed=args.seed, cancel_rate=args.cancel_rate)

    servers = []
    for i in range(args.replicas):
        name = f"r{i}"
        sched = ContinuousScheduler(
            build_engine(), batch=args.batch, chunk=args.chunk,
            policy=args.policy, prefill_chunk=args.prefill_chunk,
            age_limit=args.age_limit, adaptive=adaptive,
            faults=plan.injector(name) if plan is not None else None)
        servers.append(AsyncEngineServer(sched, name=name,
                                         queue_limit=args.queue_limit))
    router = ReplicaRouter(
        servers, seed=args.seed,
        client_faults=plan.client() if plan is not None else None)

    async def run():
        await router.start(health_every_s=0.2)
        try:
            return await router_replay(router, reqs,
                                       deadline_s=args.deadline_s)
        finally:
            await router.stop()

    results, stats = asyncio.run(run())
    drained = router.drained()
    faulty = "faults on" if args.inject_faults is not None else "faults off"
    print(f"[serve] router x{args.requests} reqs over {args.replicas} "
          f"replica(s) ({faulty}): {stats['delivered_total']} tokens in "
          f"{stats['makespan_s']:.2f}s ({stats['tok_s']:.1f} tok/s, "
          f"goodput {stats['goodput_tok_s']:.1f} tok/s), "
          f"states {stats['states']}, {stats['retries']} retried, "
          f"routed {stats['routed']}, "
          f"latency mean {stats['latency_mean_s']:.2f}s "
          f"p95 {stats['latency_p95_s']:.2f}s, "
          f"pages drained: {drained}")
    if not stats["terminal"] or not drained:
        raise SystemExit(
            f"[serve] FAULT-TOLERANCE VIOLATION: terminal="
            f"{stats['terminal']} drained={drained}")
    return results, stats


def _hcmp_gate(args, data, eng_overlap, results, build_inline,
               adaptive=None):
    """--hcmp overlap acceptance gate (the CI smoke): re-serve the SAME
    arrival stream on an inline twin engine and require bit-identical
    per-request tokens, plus a leak-free drained pool on the overlap
    engine.  Exits non-zero on any parity or leak failure."""
    leak = not (eng_overlap.sched_pool_conserved()
                and eng_overlap.sched_drained())
    if args.sched == "continuous":
        ref, _ = ContinuousScheduler(
            build_inline(), batch=args.batch, chunk=args.chunk,
            policy=args.policy, prefill_chunk=args.prefill_chunk,
            age_limit=args.age_limit, adaptive=adaptive).serve(
                _requests(args, data))
    else:
        ref, _ = serve_static(build_inline(), _requests(args, data),
                              batch=args.batch)
    bad = [r.req_id for r, s in zip(results, ref)
           if not np.array_equal(r.tokens, s.tokens)]
    hs = eng_overlap.hcmp_stats or {}
    print(f"[serve] hcmp overlap gate: parity "
          f"{'OK' if not bad else 'FAIL ' + str(bad)}, "
          f"pages {'LEAKED' if leak else 'OK'}; "
          f"predraft hits {hs.get('predraft_hits', 0)} / discards "
          f"{hs.get('predraft_discards', 0)} over {hs.get('chunks', 0)} "
          f"chunks on {hs.get('devices', 1)} device(s)")
    if bad or leak:
        raise SystemExit(f"[serve] HCMP OVERLAP VIOLATION: overlapped "
                         f"draft/verify diverged from the inline engine "
                         f"(mismatched req ids {bad}, leaked pages: "
                         f"{leak})")


def _replay(eng, args, data, cfg, adaptive=None):
    """Arrival-replay mode: Poisson request stream through the scheduler."""
    reqs = _requests(args, data)
    if args.sched == "continuous":
        results, stats = ContinuousScheduler(
            eng, batch=args.batch, chunk=args.chunk, policy=args.policy,
            prefill_chunk=args.prefill_chunk, age_limit=args.age_limit,
            adaptive=adaptive).serve(reqs)
        label = f"{args.sched}/{stats['policy']}"
        if stats["prefill_chunk"]:
            label += f"+pc{stats['prefill_chunk']}"
        if adaptive is not None:
            label += "/adaptive"
            sw = stats["strategy_switches"]
            print(f"[serve] adaptive: width {stats['width_final']} at drain, "
                  f"{len(sw)} switch(es)"
                  + (f" {[(s['from'], s['to']) for s in sw]}" if sw else ""))
    else:
        results, stats = serve_static(eng, reqs, batch=args.batch)
        label = args.sched
    al = stats.get("acceptance_length")
    print(f"[serve] {label} x{args.requests} reqs "
          f"(poisson rate {args.rate}/s, B={args.batch}): "
          f"{stats['emitted_total']} tokens in {stats['makespan_s']:.2f}s "
          f"({stats['tok_s']:.1f} tok/s aggregate), "
          + (f"acceptance length {al:.2f}, " if al is not None else "")
          + f"latency mean {stats['latency_mean_s']:.2f}s "
          f"p50 {stats['latency_p50_s']:.2f}s "
          f"p95 {stats['latency_p95_s']:.2f}s, "
          f"queue wait mean {stats['queue_wait_mean_s']:.2f}s "
          f"p95 {stats['queue_wait_p95_s']:.2f}s")
    drained = eng.sched_pool_conserved() and eng.sched_drained()
    if stats["states"] != {"DONE": len(results)} or not drained:
        raise SystemExit(f"[serve] REPLAY VIOLATION: states "
                         f"{stats['states']}, pages drained: {drained}")
    return results, stats


def _measured_tree(cfg, accs, args, build):
    """``--width 0`` on a TPU: time each candidate width's compiled step
    on this chip (``arca.profile_engine``) and keep the measured argmax.
    ``build(spec, max_len)`` makes the profiling engine, sized for the
    deepest candidate."""
    widths = (1, 2, 4, 8, 16)
    specs = {w: T.candidate_spec(accs, w) for w in widths}
    eng = build(specs[max(widths)], args.prompt_len + args.tokens + max(
        s.max_depth for s in specs.values()))
    time_fn = arca.profile_engine(eng, widths, accs=accs, batch=args.batch,
                                  prompt_len=args.prompt_len)
    strat = arca.best(arca.choose_strategy(cfg, accs, ctx=args.prompt_len,
                                           time_fn=time_fn, widths=widths))
    print(f"[serve] measured ARCA chose width={strat.width} "
          f"(E[AL]={strat.acceptance:.2f}, "
          f"step {strat.step_time * 1e3:.2f} ms)")
    return strat.tree


def main(argv=None):
    """Parse ``argv`` (default: the command line) and serve.  Returns what
    the run produced — ``(results, stats)`` for a replay, ``(tokens,
    stats)`` for a fixed batch — and raises ``SystemExit`` when a gate
    (replay states and page drain, HCMP parity, fault tolerance) fails."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b-smoke")
    ap.add_argument("--mode", default="ghidorah",
                    choices=["ghidorah", "sequential"])
    ap.add_argument("--width", type=int, default=0,
                    help="verification width (0 = let ARCA choose: by "
                         "timing each width's compiled step on a TPU, by "
                         "its analytic model elsewhere)")
    ap.add_argument("--spec-width", default=None,
                    help="verification width: an int (same as --width, "
                         "takes precedence) or 'auto' — MEASURED ARCA: the "
                         "compiled per-width steps are profiled on this "
                         "machine (arca.profile_engine), choose_strategy "
                         "runs over the measured times, and the continuous "
                         "scheduler keeps re-deciding the width at chunk "
                         "boundaries from the observed acceptance EMA "
                         "(needs --mode ghidorah --arrivals poisson "
                         "--sched continuous)")
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=8,
                    help="device-resident steps per host sync")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--arrivals", default="none", choices=["none", "poisson"],
                    help="replay a request-arrival process instead of one "
                         "fixed batch")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="poisson arrival rate, requests/sec")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of requests in the replayed stream")
    ap.add_argument("--sched", default="continuous",
                    choices=["continuous", "static"],
                    help="scheduler for --arrivals replay")
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "sjf", "lpt"],
                    help="admission policy for --sched continuous: fifo "
                         "(arrival order), sjf (smallest reserved "
                         "footprint first; may admit past a page-deferred "
                         "head-of-line request — starvation-prone under "
                         "sustained small-request load), lpt (largest "
                         "first)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="admit prompts longer than N in N-token pieces "
                         "(0 = whole-prompt admission; attention-family "
                         "engines only)")
    ap.add_argument("--age-limit", type=int, default=0,
                    help="starvation bound for --policy sjf/lpt: a request "
                         "deferred for more than N chunk boundaries is "
                         "promoted to FIFO-head priority (0 = off)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV: sequences share one page pool and "
                         "reserve pages for prompt+budget instead of a "
                         "dense max_len row each")
    ap.add_argument("--page-size", type=int, default=16,
                    help="slots per KV page (--paged)")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="paged pool storage dtype (--paged): fp32 keeps "
                         "the model-dtype float pool; int8 quantizes KV "
                         "pages with per-page dequant scales "
                         "(runtime/cache.py) — ~3.5x fewer bytes/token, "
                         "so the same pool bytes reserve more tokens")
    ap.add_argument("--tree-kernel", default="dense",
                    choices=["dense", "sparse", "auto"],
                    help="paged verify kernel (ghidorah + --paged): dense "
                         "= fused page walk + tree block; sparse = split "
                         "quantized page walk + block-masked tree kernel "
                         "merged by the Eq.-1 rule (forces the pallas "
                         "backend — the split is kernel-only); auto = "
                         "ARCA times both per shape and picks the faster")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="total reservable pages in the shared pool "
                         "(0 = dense-equivalent: batch * pages(max_len)); "
                         "shrink to serve a larger --batch at fixed memory")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--heads-ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the async router "
                         "(>1 switches the replay to the fault-tolerant "
                         "server/router plane)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (seconds, replica serve "
                         "clock); expired requests finalize TIMED_OUT at "
                         "the next chunk boundary")
    ap.add_argument("--cancel-rate", type=float, default=0.0,
                    help="fraction of clients that disconnect mid-stream "
                         "(deterministic per request id); cancelled "
                         "requests finalize CANCELLED")
    ap.add_argument("--inject-faults", type=int, default=None,
                    metavar="SEED",
                    help="arm the seeded chaos harness: replica r0 crash "
                         "(when --replicas > 1), chunk stalls, "
                         "admission-time pool exhaustion, plus "
                         "--cancel-rate disconnects; exits non-zero on "
                         "any leaked page or non-terminal request")
    ap.add_argument("--queue-limit", type=int, default=64,
                    help="bounded admission queue per replica; submits "
                         "over it are REJECTED (backpressure)")
    ap.add_argument("--hcmp", default="inline",
                    choices=["inline", "overlap", "auto"],
                    help="executor partition for the drafted engine "
                         "(core/hcmp/executors.py): inline = fused "
                         "draft+verify on one executor; overlap = "
                         "disaggregated DraftExecutor/VerifyExecutor with "
                         "draft(t+1) overlapping commit(t) — a replay "
                         "additionally re-runs the stream on an inline "
                         "twin and exits non-zero on any token mismatch "
                         "or leaked page (the CI gate); auto = ARCA times "
                         "both partitions and picks the faster "
                         "(ghidorah only)")
    args = ap.parse_args(argv)
    # ---- argument validation: fail fast with a clear error, never hang
    # or crash layers deeper --------------------------------------------
    if args.tokens < 1:
        ap.error("--tokens must be >= 1")
    if args.batch < 1:
        ap.error("--batch must be >= 1")
    if args.chunk < 1:
        ap.error("--chunk must be >= 1")
    if args.prompt_len < 2:
        ap.error("--prompt-len must be >= 2 (one context token must "
                 "survive the next-token shift)")
    if args.arrivals == "poisson":
        if args.rate <= 0:
            ap.error("--rate must be > 0 (poisson inter-arrivals are "
                     "1/rate)")
        if args.requests < 1:
            ap.error("--requests must be >= 1")
    if args.prefill_chunk < 0:
        ap.error("--prefill-chunk must be >= 0 (0 disables chunked "
                 "prefill)")
    if args.age_limit < 0:
        ap.error("--age-limit must be >= 0 (0 disables aging)")
    if args.paged and args.page_size < 1:
        ap.error("--page-size must be >= 1")
    if args.pool_pages < 0:
        ap.error("--pool-pages must be >= 0 (0 = dense-equivalent pool)")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.deadline_s is not None and args.deadline_s <= 0:
        ap.error("--deadline-s must be > 0")
    if not 0.0 <= args.cancel_rate <= 1.0:
        ap.error("--cancel-rate must be in [0, 1]")
    if args.queue_limit < 1:
        ap.error("--queue-limit must be >= 1")
    if args.spec_width and args.mode != "ghidorah":
        ap.error("--spec-width is a ghidorah option (sequential decoding "
                 "has no verification width)")
    if args.hcmp != "inline" and args.mode != "ghidorah":
        ap.error("--hcmp overlap/auto is a ghidorah option (sequential "
                 "decoding has no draft source to disaggregate)")
    if args.kv_dtype == "int8" and not args.paged:
        ap.error("--kv-dtype int8 quantizes the PAGED pool (per-page "
                 "scales live on the page axis) — add --paged")
    if args.tree_kernel != "dense":
        if not args.paged:
            ap.error("--tree-kernel sparse/auto splits the PAGED verify "
                     "path — add --paged")
        if args.mode != "ghidorah":
            ap.error("--tree-kernel sparse/auto is a ghidorah option "
                     "(sequential decoding has no verification tree)")
    if _fault_tolerant(args) and (args.arrivals != "poisson"
                                  or args.sched != "continuous"):
        ap.error("--replicas/--deadline-s/--cancel-rate/--inject-faults "
                 "need --arrivals poisson --sched continuous (the async "
                 "plane serves an arrival stream)")
    paged_kw = dict(paged=args.paged, page_size=args.page_size,
                    pool_pages=args.pool_pages or None,
                    kv_dtype=None if args.kv_dtype == "fp32"
                    else args.kv_dtype)
    if args.hcmp != "inline":
        # must run BEFORE the first jax computation: the second host
        # device can only be requested while the backend is uninitialized
        from repro.core.hcmp.executors import (ensure_host_devices,
                                               executor_pair)
        ensure_host_devices(2)
        vdev, ddev = executor_pair()
        note = "" if vdev != ddev else \
            " (one device: overlap degrades to a serial schedule)"
        print(f"[serve] hcmp {args.hcmp}: verify on {vdev}, draft on "
              f"{ddev}{note}")
        # overlap-capable engine; "auto" measures and may switch back
        paged_kw["hcmp"] = "overlap"

    cfg = get_config(args.arch)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    if args.ckpt:
        params = checkpoint.restore(args.ckpt, params)

    data = MarkovDataset(cfg.vocab_size, seed=1)
    toks = data.sample(args.batch, args.prompt_len, seed=7)[:, :-1]
    batch = {"tokens": toks.astype(np.int32)}

    if args.mode == "sequential":
        # prompt + budget slots; the sequential driver writes at most
        # prompt + (tokens - 1) entries before every row is done
        max_len = args.prompt_len + args.tokens
        eng = BatchEngine(model, params, max_len=max_len, chunk=args.chunk,
                          **paged_kw)
        if args.arrivals != "none":
            if _fault_tolerant(args):
                return _replay_async(args, data, _once_then(
                    eng, lambda: BatchEngine(model, params, max_len=max_len,
                                             chunk=args.chunk, **paged_kw)))
            return _replay(eng, args, data, cfg)
        t0 = time.perf_counter()
        out, stats = eng.generate(batch, args.tokens)
        dt = time.perf_counter() - t0
        n_out = stats["emitted_total"]       # real tokens, not EOS padding
        print(f"[serve] sequential: {n_out} tokens "
              f"({args.batch} seq x chunk {args.chunk}) in {dt:.2f}s "
              f"({n_out / dt:.1f} tok/s)")
        return out, stats

    heads = init_medusa(cfg, jax.random.PRNGKey(args.seed + 1))
    if args.heads_ckpt:
        heads = checkpoint.restore(args.heads_ckpt, heads)
    accs = T.default_accs(cfg.medusa_heads, cfg.medusa_top_k)
    if args.tree_kernel != "dense":
        # the split verify path is kernel-only: pin the pallas backend so
        # "sparse" (and auto's sparse arm) runs the real split page walk +
        # block-masked tree kernel, not the fused ref fallback
        paged_kw["backend"] = "pallas"
        if args.tree_kernel == "sparse":
            paged_kw["tree_kernel"] = "sparse"
        print(f"[serve] tree kernel {args.tree_kernel}: pallas backend")
    auto = args.spec_width == "auto"
    if args.spec_width and not auto:
        args.width = int(args.spec_width)
    if auto:
        # measured ARCA + runtime-adaptive speculation: profile the
        # compiled per-width steps on THIS machine, start at the measured
        # argmax, and let the scheduler re-decide at chunk boundaries
        if args.arrivals == "none" or args.sched != "continuous":
            ap.error("--spec-width auto needs --arrivals poisson "
                     "--sched continuous")
        widths = (1, 2, 4, 8, 16)
        specs = {w: T.candidate_spec(accs, w) for w in widths}
        # size the ring for the DEEPEST candidate: a runtime switch must
        # never outgrow a resident row's capacity
        max_len = args.prompt_len + args.tokens + max(
            s.max_depth for s in specs.values())
        eng = SpeculativeEngine(model, heads, params, specs[max(widths)],
                                max_len=max_len, chunk=args.chunk,
                                **paged_kw)
        time_fn = arca.profile_engine(eng, widths, accs=accs,
                                      batch=args.batch,
                                      prompt_len=args.prompt_len,
                                      tree_kernels=("dense", "sparse")
                                      if args.tree_kernel == "auto"
                                      else None)
        strategies = arca.choose_strategy(cfg, accs, ctx=args.prompt_len,
                                          time_fn=time_fn, widths=widths)
        start = arca.best(strategies)
        print(f"[serve] measured ARCA: start width={start.width} "
              f"(E[AL]={start.acceptance:.2f}, "
              f"step {start.step_time * 1e3:.2f} ms)")
        eng.set_strategy(start.tree)
        if args.tree_kernel == "auto":
            # choose_strategy stamped the measured kernel winner on each
            # Strategy the same way it stamped the partition
            print(f"[serve] tree kernel: {start.tree_kernel} "
                  f"(measured winner for width {start.width})")
            eng.set_tree_kernel(start.tree_kernel)
        if args.hcmp != "inline":
            # profile_engine timed BOTH partitions (the engine was built
            # overlap-capable), so choose_strategy stamped the measured
            # winner on each Strategy; "auto" follows it, "overlap" pins
            part = "overlap" if args.hcmp == "overlap" else start.hcmp
            print(f"[serve] hcmp partition: {part} "
                  f"(measured winner for width {start.width}: "
                  f"{start.hcmp})")
            eng.set_hcmp(part)

        def build_auto():
            e = SpeculativeEngine(model, heads, params, specs[max(widths)],
                                  max_len=max_len, chunk=args.chunk,
                                  **paged_kw)
            e.set_strategy(start.tree)
            if args.tree_kernel == "auto":
                e.set_tree_kernel(eng.tree_kernel)
            if args.hcmp != "inline":
                e.set_hcmp(eng.hcmp)
            return e

        if _fault_tolerant(args):
            return _replay_async(args, data, _once_then(eng, build_auto),
                                 adaptive=strategies)
        results, stats = _replay(eng, args, data, cfg, adaptive=strategies)
        if args.hcmp == "overlap":
            def build_inline():
                e = SpeculativeEngine(model, heads, params,
                                      specs[max(widths)],
                                      max_len=max_len, chunk=args.chunk,
                                      **{**paged_kw, "hcmp": "inline"})
                e.set_strategy(start.tree)
                return e
            _hcmp_gate(args, data, eng, results, build_inline,
                       adaptive=strategies)
        return results, stats
    if args.width:
        spec = T.build_tree(accs, args.width)
    elif jax.default_backend() == "tpu":
        spec = _measured_tree(cfg, accs, args, lambda sp, n: SpeculativeEngine(
            model, heads, params, sp, max_len=n, chunk=args.chunk,
            **paged_kw))
    else:
        # the analytic Jetson model stands in only where no chip can be
        # timed (the CPU benches that exercise it)
        strat = arca.best(arca.choose_strategy(cfg, accs, ctx=args.prompt_len))
        spec = strat.tree
        print(f"[serve] ARCA chose width={strat.width} "
              f"(E[AL]={strat.acceptance:.2f})")
    # one speculative step past the budget can commit up to max_depth
    # tokens, so size the ring for the worst-case overshoot — the old
    # ``+ 8`` slack was smaller than the overshoot and the ring wrapped
    max_len = args.prompt_len + args.tokens + spec.max_depth
    eng = SpeculativeEngine(model, heads, params, spec, max_len=max_len,
                            chunk=args.chunk, **paged_kw)
    if args.hcmp == "auto" or args.tree_kernel == "auto":
        # measure the partition / verify kernel for THIS shape on THIS
        # machine: time the compiled step under each candidate layout at
        # the serving batch and keep the faster (same decision path
        # --spec-width auto takes through choose_strategy's Strategy
        # hcmp/tree_kernel stamps)
        modes = {"auto": ("inline", "overlap"), "overlap": ("overlap",),
                 "inline": ("inline",)}[args.hcmp]
        tks = ("dense", "sparse") if args.tree_kernel == "auto" \
            else (args.tree_kernel,)
        tf = arca.profile_engine(eng, (spec.width,), accs=accs,
                                 batch=args.batch,
                                 prompt_len=args.prompt_len,
                                 hcmp_modes=modes, tree_kernels=tks)
        key = (spec.width, spec.max_depth, spec.n_paths, args.batch)
        if args.hcmp == "auto":
            part = tf.partition_for(spec)
            print(f"[serve] measured partition: {part} "
                  f"(inline {tf.times[key + ('inline',)] * 1e3:.2f} ms, "
                  f"overlap {tf.times[key + ('overlap',)] * 1e3:.2f} ms "
                  f"per step)")
            eng.set_hcmp(part)
        if args.tree_kernel == "auto":
            tk = tf.kernel_for(spec)
            mode = tf.partition_for(spec)
            print(f"[serve] measured tree kernel: {tk} (dense "
                  f"{tf.times[key + (mode, 'dense')] * 1e3:.2f} ms, sparse "
                  f"{tf.times[key + (mode, 'sparse')] * 1e3:.2f} ms "
                  f"per step)")
            eng.set_tree_kernel(tk)
    if args.arrivals != "none":
        if _fault_tolerant(args):
            return _replay_async(args, data, _once_then(
                eng, lambda: SpeculativeEngine(model, heads, params, spec,
                                               max_len=max_len,
                                               chunk=args.chunk,
                                               **paged_kw)))
        results, stats = _replay(eng, args, data, cfg)
        if args.hcmp == "overlap":
            _hcmp_gate(args, data, eng, results,
                       lambda: SpeculativeEngine(
                           model, heads, params, spec, max_len=max_len,
                           chunk=args.chunk,
                           **{**paged_kw, "hcmp": "inline"}))
        return results, stats
    t0 = time.perf_counter()
    out, stats = eng.generate(batch, args.tokens)        # full batch: B >= 1
    dt = time.perf_counter() - t0
    n_out = stats["emitted_total"]           # real tokens, not EOS padding
    print(f"[serve] ghidorah: {n_out} tokens "
          f"({args.batch} seq x chunk {args.chunk}) in {dt:.2f}s "
          f"({n_out / dt:.1f} tok/s), "
          f"acceptance length {stats['acceptance_length']:.2f} "
          f"over {stats['steps']} seq-steps")
    if args.hcmp == "overlap":
        # fixed-batch parity gate: the overlapped schedule must emit the
        # exact token stream of the fused inline engine
        ref = SpeculativeEngine(model, heads, params, spec, max_len=max_len,
                                chunk=args.chunk,
                                **{**paged_kw, "hcmp": "inline"})
        ref_out, _ = ref.generate(batch, args.tokens)
        hs = eng.hcmp_stats or {}
        ok = np.array_equal(np.asarray(out), np.asarray(ref_out))
        print(f"[serve] hcmp overlap gate: parity "
              f"{'OK' if ok else 'FAIL'}; predraft hits "
              f"{hs.get('predraft_hits', 0)} / discards "
              f"{hs.get('predraft_discards', 0)} over "
              f"{hs.get('chunks', 0)} chunks on "
              f"{hs.get('devices', 1)} device(s)")
        if not ok:
            raise SystemExit("[serve] HCMP OVERLAP VIOLATION: overlapped "
                             "draft/verify diverged from the inline "
                             "engine on the fixed batch")
    return out, stats


if __name__ == "__main__":
    main()
