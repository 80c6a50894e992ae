#!/usr/bin/env python3
"""Readings that a cell's output-check limit is set from, in one process.

    python3 bench/limits.py --workload <cell> --seeds 12 --control 3 \
        --seconds <s> [--first-seed n]

For each seed the cell's own system (built and warmed once; each seed
brings its own weights and traffic) serves a window of ``--seconds``, and
the largest logit gap of its served tokens is read exactly as a run reads
it.  For the first ``--control`` seeds the control is read on the same
prompts and served tokens: the reference computed in int8, one precision
below the configuration's bfloat16, taking the token its logits put
first at each position.  The limit goes between the two readings
(``bench/checks/<cell>.json``); ``PERF.md`` records them.

Prints one JSON line per seed and a summary line.  Needs the chip, like
a run; the benchmark's own runs never call this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _summary(harness, gaps) -> dict:
    """The check's statistics, and the share of positions whose token is
    not the reference's best."""
    import numpy as np
    out = harness.gap_stats(gaps)
    g = np.concatenate([a for a, _ in gaps]) if gaps else np.zeros((0,))
    out["flip_share"] = float((g > 0).mean()) if g.size else 0.0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, traffic as tm, weights

    spec, cell, centry = harness.find_cell(ROOT, args.workload)
    config = harness.load_json(ROOT / centry["file"])
    bench = ROOT / "bench"
    traffic = harness.load_json(bench / "traffic" / f"{cell['traffic']}.json")
    ref = harness.load_module(bench / "references"
                              / f"{config['reference']}.py")
    harness.device_info(int(cell["chips"]))
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = harness.CompileClock().install()
    sys_ = harness.build(config, traffic, args.first_seed)
    harness.warm_up(sys_, args.first_seed)
    pad = harness._pad(tm.max_context(traffic))
    prog, ctrl = [], []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        if i:
            # one set of weights on the chip at a time
            sys_.engine.params = sys_.engine.heads = None
            sys_.params = sys_.heads = None
            gc.collect()
            sys_.params, sys_.heads = weights.build(sys_.dims, seed)
            sys_.engine.params = sys_.params
            sys_.engine.heads = sys_.heads
        win = harness.run_window(sys_, traffic, seed, args.seconds, None,
                                 clock)
        sample = harness.sample_for_check(
            win, int(traffic["check_requests"]), seed)
        gaps_p, gaps_c = [], []
        for r in sample:
            prompt, toks = win.served[r["index"]]
            gaps_p.append(harness.logit_gaps(ref, sys_.params, sys_.dims,
                                             prompt, toks, pad))
            if i < args.control:
                gaps_c.append(harness.logit_gaps(ref, sys_.params, sys_.dims,
                                                 prompt, toks, pad,
                                                 mode="int8"))
        g = _summary(harness, gaps_p)
        prog.append(g)
        line = {"seed": seed, "program": g, "requests": len(win.requests),
                "compiles": win.compiles}
        if gaps_c:
            line["control"] = _summary(harness, gaps_c)
            ctrl.append(line["control"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "program": prog,
                      "control": ctrl,
                      "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
