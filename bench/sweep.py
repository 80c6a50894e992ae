#!/usr/bin/env python3
"""Sweep an open-loop cell's arrival rate once, to find its knee.

    python3 bench/sweep.py --workload <cell> --rates 2 3 4 6 --seconds 30

Builds and warms the cell's system once, then serves the cell's traffic
at each rate in turn (the rate in the traffic file is replaced; the seed
is the same at every rate) and prints, per rate, the requests that
arrived, the backlog at the window's close (arrived and not finished),
and the end-to-end numbers.  The knee is the highest rate whose backlog
does not grow with the window; a cell runs at about four fifths of it,
and the rate taken is written into the cell's traffic file by hand.
Needs the chip; the benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=424242)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, latency

    _, cell, centry = harness.find_cell(ROOT, args.workload)
    config = harness.load_json(ROOT / centry["file"])
    traffic = harness.load_json(ROOT / "bench" / "traffic"
                                / f"{cell['traffic']}.json")
    if traffic["loop"] != "open":
        raise SystemExit("a sweep is for open-loop traffic")
    harness.device_info(int(cell["chips"]))
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = harness.CompileClock().install()
    sys_ = harness.build(config, traffic, args.seed,
                         harness.architecture(ROOT, config))
    harness.warm_up(sys_, args.seed)
    T = args.seconds
    for rate in args.rates:
        tr = dict(traffic, rate=rate, drain_s=min(traffic["drain_s"], 15))
        win = harness.run_window(sys_, tr, args.seed, T, None, clock)
        arrived = latency.arrived(win.requests, T)
        backlog = sum(1 for r in arrived
                      if not r["done"] or r["last"] > T)
        late_half = [r for r in arrived if r["arrival"] >= T / 2]
        print(json.dumps({
            "rate": rate, "arrived": len(arrived), "backlog_at_close":
            backlog, "unfinished_after_drain": sum(
                1 for r in arrived if not r["done"]),
            "ttft_p95_ms": latency.ttft_ms(win.requests, T, 95),
            "ttft_p95_ms_second_half": latency.ttft_ms(late_half, T, 95),
            "queue_wait_p95_ms": latency.queue_wait_ms(win.requests, T, 95),
            "tpot_p50_ms": latency.tpot_ms(win.requests, T, 50),
            "output_tok_s": latency.rate(win.tokens_in_window, T),
            "compiles": win.compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
