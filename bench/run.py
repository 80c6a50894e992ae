#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and, last, ``checks``: each number the output
check compared, with its limit.  The same checks close standard error.
Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the program is not beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_out" / "trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.runtime.scheduler  # noqa: F401
    except ImportError as e:
        print(f"[bench] the program (src/repro) is not beside the "
              f"benchmark: {e}", file=sys.stderr)
        return 2
    from bench import harness

    trace_dir = None
    if args.trace:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
    try:
        result = harness.execute(ROOT, args.workload, args.seed,
                                 args.seconds, bool(args.trace), T_PROCESS0,
                                 trace_dir=trace_dir)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    finally:
        if trace_dir:
            import shutil
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
