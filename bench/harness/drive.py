"""Warm-up and the measured window, both through ``ServeEngine.submit``.

Every read's due time, submit time and answer time are taken on the
monotonic clock by the benchmark itself: the answer time in the
future's done-callback, the due time from the schedule.  A read is
timed from when it was due, so a generator that falls behind shows as
latency and not as a faster server.
"""
from __future__ import annotations

import concurrent.futures as cf
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from .traffic import Reads

SETTLE_S = 60.0  # how long past the close an answer may still come


@dataclass
class Window:
    """What the window offered and what came back, per read."""

    t_open: float
    t_close: float
    lengths: np.ndarray  # [n] bases of each offered read
    due: np.ndarray  # [n] monotonic due time
    submitted: np.ndarray  # [n] monotonic time submit() was called
    done: np.ndarray  # [n] monotonic answer time (nan: none yet)
    futures: list = field(default_factory=list)
    results: list = field(default_factory=list)  # ServeResult or None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def answered(self) -> np.ndarray:
        """Reads with an answer (after `settle`; until then, a time)."""
        if self.results:
            return np.array([r is not None for r in self.results], bool)
        return np.isfinite(self.done)

    def answered_in_window(self) -> np.ndarray:
        return (self.answered() & (self.done >= self.t_open)
                & (self.done <= self.t_close))


class _Recorder:
    """Submits reads and stamps each answer's time as it resolves."""

    def __init__(self, engine, reads: Reads, n_max: int):
        self.engine = engine
        self.reads = reads
        self.cv = threading.Condition()
        self.outstanding = 0
        self.due = np.full(n_max, np.nan)
        self.submitted = np.full(n_max, np.nan)
        self.done = np.full(n_max, np.nan)
        self.futures: list = []

    def submit(self, i: int, due: float) -> None:
        self.due[i] = due
        self.submitted[i] = time.monotonic()
        with self.cv:
            self.outstanding += 1
        fut = self.engine.submit(self.reads.read(i))
        self.futures.append(fut)
        fut.add_done_callback(lambda _f, i=i: self._answered(i))

    def _answered(self, i: int) -> None:
        self.done[i] = time.monotonic()
        with self.cv:
            self.outstanding -= 1
            self.cv.notify_all()

    def window(self, t_open: float, t_close: float) -> Window:
        n = len(self.futures)
        return Window(t_open, t_close,
                      lengths=self.reads.lengths[:n].astype(np.int64),
                      due=self.due[:n], submitted=self.submitted[:n],
                      done=self.done[:n], futures=self.futures)


def backlog(engine, reads: Reads, seconds: float, outstanding: int) -> Window:
    """Keep ``outstanding`` reads in flight for ``seconds``."""
    rec = _Recorder(engine, reads, len(reads))
    t_open = time.monotonic()
    t_close = t_open + seconds
    i = 0
    while i < len(reads):
        with rec.cv:
            while rec.outstanding >= outstanding:
                left = t_close - time.monotonic()
                if left <= 0:
                    break
                rec.cv.wait(left)
        now = time.monotonic()
        if now >= t_close:
            break
        rec.submit(i, now)
        i += 1
    while time.monotonic() < t_close:  # pool spent: the window still runs
        time.sleep(min(0.01, max(t_close - time.monotonic(), 0)))
    return rec.window(t_open, t_close)


def poisson(engine, reads: Reads, offsets: np.ndarray) -> Window:
    """Submit read ``i`` at ``offsets[i]`` seconds after the open."""
    rec = _Recorder(engine, reads, len(reads))
    t_open = time.monotonic()
    for i, off in enumerate(offsets):
        due = t_open + off
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        rec.submit(i, due)
    t_close = t_open + float(offsets[-1])
    return rec.window(t_open, max(t_close, time.monotonic()))


def settle(win: Window, settle_s: float = SETTLE_S) -> None:
    """Wait for the answers still due, up to ``settle_s`` past the close."""
    left = win.t_close + settle_s - time.monotonic()
    cf.wait(win.futures, timeout=max(left, 0.0))
    for f in win.futures:
        ok = f.done() and not f.cancelled() and f.exception() is None
        win.results.append(f.result() if ok else None)


def warm_up(engine, reads: Reads, need_caps: set[int], make_read) -> dict:
    """Run every shape the window will use; returns the trace counts.

    For each bucket rung the window can reach: batches of every size
    from one read to ``max_batch`` (partial flushes are padded to the
    batch, and a graph flush's tile-count rung follows the survivors in
    it), then the warm-up reads left over at once, as a backlog.  Every
    read is submitted once, so none is a result-cache hit;
    ``make_read(cap)`` supplies fresh reads for a rung that the warm-up
    reads run short of.
    """
    cfg = engine.config
    mb = cfg.max_batch
    sizes = sorted({1, 2, 3} | {max(1, mb * f // 8) for f in range(1, 9)})
    by_cap: dict[int, list[np.ndarray]] = {c: [] for c in need_caps}
    for i in range(len(reads)):
        cap = cfg.bucket_for(int(reads.lengths[i]))
        if cap in by_cap:
            by_cap[cap].append(reads.read(i))

    def batch(cap: int, size: int) -> None:
        pool = by_cap[cap]
        futs = [engine.submit(pool.pop() if pool else make_read(cap))
                for _ in range(size)]
        for f in futs:
            f.result()

    for cap in sorted(by_cap):
        for _ in range(2):
            for size in sizes:
                batch(cap, size)
    futs = [engine.submit(r) for pool in by_cap.values() for r in pool]
    for f in futs:
        f.result()
    return dict(engine.trace_counts)
