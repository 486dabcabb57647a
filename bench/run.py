#!/usr/bin/env python3
"""Run one cell of the read-mapping benchmark once; print its result line.

    python3 bench/run.py --workload linear-sr-batch --seed 7 --seconds 10 \
        --trace 0

The cell, its configuration, traffic mix, metrics and correctness limits
are found by name from ``BENCHMARK.json`` (see ``bench/harness``).  The
run needs a TPU: with no accelerator, or fewer chips than the cell asks
for, it exits non-zero and prints no result.  ``--trace 1`` reports the
per-layer metrics from the service's spans and the profiler's trace;
``--trace 0`` the end-to-end ones.  ``--control`` runs the engine with
the cell's control settings (``bench/checks/<cell>.json``), which must
come out not correct; the benchmark's own runs never pass it.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import cell, check

    try:
        result = cell.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               control=args.control)
    except cell.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    checks = {k: (v["value"], v["limit"]) for k, v in result["checks"].items()}
    for line in check.report_lines(checks):
        print(line, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
