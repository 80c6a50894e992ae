#!/usr/bin/env python3
"""Time the two dropless expert dispatches of a sparse-expert benchmark
configuration on the device this process finds: the serving path's
sorted grouped matmul (``models/mlp.moe_dropless``) against the one-hot
capacity dispatch (``models/mlp.moe_apply``) at capacity = group size,
which drops nothing either.

    python3 benchmarks/moe_dispatch.py --workload mellum2-12b-a2.5b.code-1

For each dispatch a served engine (paged, Medusa tree, the cell's
serving settings, one set of the benchmark's seeded weights) is timed on
one decode step (``DecodeEngine.time_step``: best of ``--reps``
chunks at ``--context`` cached tokens, divided by the chunk length) and
on one 128-token prefill piece (``sched_extend``, median of ``--reps``).
Prints one JSON line.  Times are the host clock around work that ends in
``block_until_ready``; run it on the chip.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _onehot(cfg, lp, h, stacks):
    from repro.models import mlp
    cap = cfg.num_experts / cfg.experts_per_token   # capacity = group size
    p = dict(lp["moe"], **{k: w[lp["moe"]["layer"]]
                           for k, w in stacks.items()})
    return mlp.moe_apply(cfg, p, h, capacity_factor=cap)[0], None


def _time(eng, C, vocab, seed, *, reps, context):
    """(decode step ms, 128-token piece ms: median, min) of one engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    step_s = eng.time_step(prompt_len=context, reps=reps)
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, vocab, (1, C)), jnp.int32)
    row = eng.sched_prefill({"tokens": toks})
    st = eng.sched_blank(row, 1)
    st, _ = eng.sched_admit(st, 0, {"tokens": toks}, n_tokens=16,
                            reserve_len=C * (reps + 2))
    st, last = eng.sched_extend(st, 0, toks, C)          # compiles
    jax.block_until_ready(last)
    piece = []
    for _ in range(reps):
        t0 = time.perf_counter()
        st, last = eng.sched_extend(st, 0, toks, C)
        jax.block_until_ready(last)
        piece.append(time.perf_counter() - t0)
    return {"decode_step_ms": 1e3 * step_s,
            "piece_ms_median": 1e3 * statistics.median(piece),
            "piece_ms_min": 1e3 * min(piece)}


def measure(config, traffic, seed, *, reps, context) -> dict:
    """Both dispatches on one set of weights: the served engine as the
    harness builds it, then an engine like it whose experts run the
    one-hot dispatch (its programs are traced afresh)."""
    from bench import harness
    from repro.models import transformer
    from repro.runtime.engine import DecodeEngine

    arch = harness.architecture(ROOT, config)
    sys_ = harness.build(config, traffic, seed, arch)
    eng, C = sys_.engine, sys_.sched.prefill_chunk
    vocab = sys_.dims["vocab"]
    out = {"sorted": _time(eng, C, vocab, seed, reps=reps, context=context)}
    real = transformer._serve_mix
    transformer._serve_mix = _onehot
    try:
        onehot = DecodeEngine(eng.model, sys_.params, heads=sys_.heads,
                              strategy=eng.strategy, max_len=eng.max_len,
                              chunk=eng.chunk, paged=True,
                              page_size=eng.page_size)
        out["onehot"] = _time(onehot, C, vocab, seed, reps=reps,
                              context=context)
    finally:
        transformer._serve_mix = real
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--context", type=int, default=2048)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness
    _, cell, centry = harness.find_cell(ROOT, args.workload)
    config = harness.load_json(ROOT / centry["file"])
    traffic = harness.load_json(ROOT / "bench/traffic"
                                / f"{cell['traffic']}.json")
    dev = jax.devices()[0]
    out = {"workload": args.workload, "device": dev.device_kind,
           "context": args.context}
    out.update(measure(config, traffic, args.seed, reps=args.reps,
                       context=args.context))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
