#!/usr/bin/env python3
"""Several windows of one cell after one set-up, for tuning a cell.

    python3 bench/sweep.py --workload linear-sr-online --seed 11 \
        --windows 8@1000,8@2000,30
    python3 bench/sweep.py --workload linear-sr-batch --seed 12 \
        --windows 30,30,60

The cell is set up and warmed up once, exactly as ``bench/run.py`` does
it (``cell.set_up``).  Each entry of ``--windows`` is a window's seconds,
and for an open-loop cell optionally ``@rate`` in reads per second, which
replaces the mix's rate and nothing else.  Each window offers fresh reads
and prints one JSON line: the cell's end-to-end metrics through their own
reducers, the 95th latency percentile where reads have due times, reads
answered per second, the median latency of the window's last quarter
over its first (a backlog that grows shows as a ratio well above 1), the
generator's lag and the compilations inside the window.  Used once to
find an open-loop cell's knee, the highest rate without a growing
backlog; the cell's traffic file then fixes its rate below it.

``--stall-dump FILE`` writes every thread's stack to ``FILE`` whenever
the generator makes no progress for ``STALL_S``, and logs garbage
collections longer than 20 ms to standard error.
"""
import argparse
import faulthandler
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
STALL_S = 0.25  # generator gaps are under 10 ms at the rates swept


class _Watched:
    """The engine, with a stack dump armed at each submit: a generator
    that stops submitting for ``after_s`` triggers it."""

    def __init__(self, engine, out, after_s: float):
        self._engine, self._out, self._after = engine, out, after_s
        self._armed = 0.0

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def submit(self, read):
        now = time.monotonic()
        if now - self._armed > 0.05:
            faulthandler.dump_traceback_later(self._after, file=self._out)
            self._armed = now
        return self._engine.submit(read)


def _gc_logger():
    start = {}

    def cb(phase, info):
        if phase == "start":
            start["t"] = time.monotonic()
        elif "t" in start:
            ms = (time.monotonic() - start.pop("t")) * 1e3
            if ms > 20:
                print(f"gc: generation {info['generation']} took {ms:.1f} ms",
                      file=sys.stderr, flush=True)
    return cb


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", required=True)
    ap.add_argument("--stall-dump")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import cell

    windows = [(float(s), float(r) if r else None) for s, _, r in
               (w.partition("@") for w in args.windows.split(","))]
    t_process = time.monotonic()
    svc = cell.set_up(ROOT, args.workload, args.seed, windows[0][0], False)
    engine, dump, gc_log = svc.engine, None, None
    if args.stall_dump:
        dump = open(args.stall_dump, "w")
        engine = _Watched(engine, dump, STALL_S)
        gc_log = _gc_logger()
        gc.callbacks.append(gc_log)
    try:
        _sweep(svc, engine, windows, args.seed, t_process)
    finally:
        faulthandler.cancel_dump_traceback_later()
        svc.engine.close()
        if dump is not None:
            gc.callbacks.remove(gc_log)
            dump.close()
    return 0


def _sweep(svc, engine, windows, seed: int, t_process: float) -> None:
    from bench.harness import cell, drive, traffic
    from bench.reducers import latency_quantile, window_rate

    metrics = svc.man.metrics_for(svc.cell["name"], False)
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - t_process
    for k, (seconds, rate) in enumerate(windows):
        mix = dict(svc.mix)
        if rate is not None:
            mix["rate_reads_per_s"] = rate
        rngs = [np.random.default_rng(s) for s in
                np.random.SeedSequence([seed, k]).spawn(3)]
        plan = traffic.plan(mix, svc.source, seconds=seconds,
                            max_batch=svc.engine.config.max_batch,
                            rng_warm=rngs[0], rng_window=rngs[1],
                            rng_arrival=rngs[2])
        before = sum(svc.engine.trace_counts.values())
        t0 = time.monotonic()
        win = cell.window(engine, plan, seconds)
        faulthandler.cancel_dump_traceback_later()
        drive.settle(win)
        ctx = cell.Context(win, setup_s, [], None)
        row = {"window": k, "seconds": seconds, "rate": rate,
               "t_open_s": t0 - t_process,
               "metrics": {n: v["value"] for n, v in
                           cell.reduce_metrics(svc.man, metrics, ctx).items()},
               "answered_per_s": window_rate.reduce(ctx, of="reads")}
        if plan.due is not None:
            lat = np.where(win.answered(), win.done, np.inf) - win.due
            q = max(len(lat) // 4, 1)
            row["p95_ms"] = latency_quantile.reduce(ctx, q=0.95)
            row["late_over_early"] = float(np.median(lat[-q:])
                                           / np.median(lat[:q]))
            row["lag_p99_ms"] = cell.generator_lag(win) * 1e3
            row["lag_max_ms"] = float(np.max(win.submitted - win.due)) * 1e3
        row["unanswered"] = int(sum(r is None for r in win.results))
        row["window_compiles"] = (sum(svc.engine.trace_counts.values())
                                  - before)
        print(json.dumps(row), flush=True)
        time.sleep(0.5)
    gc.unfreeze()


if __name__ == "__main__":
    sys.exit(main())
