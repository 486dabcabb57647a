"""The generator and the open-loop clock: latency counts from the due time."""
import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np

from bench.harness import drive, traffic
from bench.reducers import latency_quantile, window_rate

PROFILE = {"rate": 0.05, "sub": 0.8, "ins": 0.1, "del": 0.1}


class InstantEngine:
    """Answers every read at once; ``stall_at`` blocks one submit."""

    def __init__(self, stall_at=None, stall_s=0.0):
        self.n = 0
        self.stall_at, self.stall_s = stall_at, stall_s

    def submit(self, read):
        if self.n == self.stall_at:
            time.sleep(self.stall_s)
        self.n += 1
        f = Future()
        f.set_result(SimpleNamespace(position=0))
        return f


def reads(n, length=50):
    ref = np.random.default_rng(0).integers(0, 4, 10_000, dtype=np.int8)
    return traffic.simulate_reads(ref, np.full(n, length), PROFILE,
                                  np.random.default_rng(1))


def test_class_shares_and_gaps_are_the_same_for_every_seed():
    mix = {"reads": [{"length": 150, "share": 0.8},
                     {"length": 250, "share": 0.2}]}
    a = traffic.class_lengths(mix, 1000, np.random.default_rng(1))
    b = traffic.class_lengths(mix, 1000, np.random.default_rng(2))
    assert np.sum(a == 250) == np.sum(b == 250) == 200
    assert not np.array_equal(a, b)
    oa = traffic.poisson_offsets(500, 4.0, np.random.default_rng(1))
    ob = traffic.poisson_offsets(500, 4.0, np.random.default_rng(2))
    assert len(oa) == 2000 and abs(oa[-1] - 4.0) < 1e-9
    assert np.allclose(np.sort(np.diff(oa, prepend=0.0)),
                       np.sort(np.diff(ob, prepend=0.0)))


def test_simulated_reads_follow_the_profile():
    r = reads(2000, 150)
    assert len(r) == 2000
    assert abs(r.lengths.mean() - 150) < 1.0  # insertions ~ deletions
    assert set(np.unique(r.flat)) <= {0, 1, 2, 3}


def test_latency_is_timed_from_the_due_time_and_a_stall_shows():
    rs = reads(40)
    offsets = np.linspace(0.001, 0.2, 40)
    calm = drive.poisson(InstantEngine(), rs, offsets)
    drive.settle(calm, 1.0)
    stalled = drive.poisson(InstantEngine(stall_at=10, stall_s=0.15), rs,
                            offsets)
    drive.settle(stalled, 1.0)
    p95 = [latency_quantile.reduce(SimpleNamespace(window=w), q=0.95)
           for w in (calm, stalled)]
    assert p95[0] < 20.0  # ms: instant answers, on time
    assert p95[1] > 100.0  # reads due during the stall wait it out
    lag = stalled.submitted - stalled.due
    assert lag[11] > 0.1 and lag[0] < 0.02


def test_backlog_keeps_the_outstanding_reads_and_counts_the_window():
    class SlowEngine(InstantEngine):
        def __init__(self):
            super().__init__()
            self.live = 0
            self.peak = 0
            self.lock = threading.Lock()

        def submit(self, read):
            f = Future()
            with self.lock:
                self.live += 1
                self.peak = max(self.peak, self.live)

            def answer():
                time.sleep(0.002)
                with self.lock:
                    self.live -= 1
                f.set_result(SimpleNamespace(position=0))
            threading.Thread(target=answer).start()
            return f

    eng = SlowEngine()
    w = drive.backlog(eng, reads(5000), 0.3, outstanding=8)
    drive.settle(w, 1.0)
    assert eng.peak <= 8
    rate = window_rate.reduce(SimpleNamespace(window=w), of="reads")
    assert 0 < rate <= len(w.results) / 0.3
