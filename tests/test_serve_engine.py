"""repro.serve: buckets, deadline flush, executor/result caches, pipeline."""
import threading
import time

import numpy as np
import pytest

from repro.core import minimizer_index
from repro.genomics import pipeline, simulate
from repro.launch import serve_genomics
from repro.serve import EngineConfig, ResultCache, ServeEngine
from repro.serve.metrics import Metrics


@pytest.fixture(scope="module")
def ref():
    return simulate.random_reference(4000, seed=11)


@pytest.fixture(scope="module")
def epi(ref):
    return minimizer_index.build_epoched_index(ref, w=8, k=12)


@pytest.fixture(scope="module")
def reads(ref):
    short = simulate.simulate_reads(ref, n_reads=10, read_len=90,
                                    profile=simulate.ILLUMINA, seed=3)
    long = simulate.simulate_reads(ref, n_reads=2, read_len=150,
                                   profile=simulate.ILLUMINA, seed=4)
    return short, long


@pytest.fixture(scope="module")
def engine(epi):
    cfg = EngineConfig(buckets=(96, 192), max_batch=4, max_delay_s=0.02,
                       filter_k=10, minimizer_w=8, minimizer_k=12)
    eng = ServeEngine(epi, cfg)
    yield eng
    eng.close()


def test_bucket_selection_and_validation():
    cfg = EngineConfig(buckets=(160, 96))  # unsorted on purpose
    assert cfg.buckets == (96, 160)
    assert cfg.bucket_for(1) == 96
    assert cfg.bucket_for(96) == 96
    assert cfg.bucket_for(97) == 160
    assert cfg.bucket_for(500) == 160  # beyond the ladder: trim to top rung
    with pytest.raises(ValueError):
        EngineConfig(buckets=(100,))  # not a multiple of 32
    with pytest.raises(ValueError):
        EngineConfig(buckets=())


def test_engine_maps_and_accounts_occupancy(engine, reads):
    short, long = reads
    res = engine.map_all(list(short.reads) + list(long.reads))
    ok = sum(abs(r.position - tp) <= 16
             for r, tp in zip(res, list(short.true_pos) + list(long.true_pos)))
    assert ok >= 10  # ≥80% placed at 5% error
    assert {r.bucket_cap for r in res} == {96, 192}
    m = engine.metrics.snapshot()
    # every admitted base is either useful or accounted padding
    total = sum(min(r.read_len, r.bucket_cap) for r in res)
    assert m["bases_useful"] == total
    assert m["bases_padded_read"] == sum(
        r.bucket_cap - min(r.read_len, r.bucket_cap) for r in res)
    assert m["batch_occupancy_count"] == m["batches_flushed"] >= 3
    assert 0.0 < m["batch_occupancy_mean"] <= 1.0


def test_executor_cache_one_trace_per_bucket(engine, reads):
    short, long = reads
    engine.map_all(list(short.reads))  # repeat traffic into both buckets
    engine.map_all(list(long.reads))
    assert engine.n_executors == 2  # one per (bucket_cap, config)
    # linear executors trace their seed_filter and align stages once per
    # bucket cap (the two-jit split that makes stage timing observable)
    assert engine.trace_counts == {
        (96, "seed_filter"): 1, (96, "align"): 1,
        (192, "seed_filter"): 1, (192, "align"): 1}


class _FakeClock:
    """Deterministic monotonic clock the test advances by hand.

    The engine worker re-polls its deadline at least every 50ms of real
    time, so a fake-clock advance is observed promptly without the test
    ever racing a real wall-clock deadline."""

    def __init__(self):
        import threading
        self._t = 0.0
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            return self._t

    def advance(self, dt):
        with self._lock:
            self._t += dt


def test_deadline_triggered_flush(epi, reads):
    short, _ = reads
    clk = _FakeClock()
    cfg = EngineConfig(buckets=(96,), max_batch=8, max_delay_s=0.03,
                       filter_k=10, minimizer_w=8, minimizer_k=12)
    with ServeEngine(epi, cfg, clock=clk) as eng:
        futs = [eng.submit(r) for r in short.reads[:3]]
        # fake time is frozen before the deadline: the partial batch
        # must stay parked no matter how long compile/dispatch takes
        time.sleep(0.15)
        assert not any(f.done() for f in futs)
        clk.advance(1.0)  # past max_delay_s → deadline flush
        res = [f.result(timeout=30) for f in futs]  # flushes despite 3 < 8
    assert all(r.position >= 0 or r.position == -1 for r in res)
    m = eng.metrics.snapshot()
    assert m["batches_flushed"] == 1
    assert m["batch_occupancy_mean"] == pytest.approx(3 / 8)


def test_result_cache_hit_and_epoch_invalidation(ref, reads):
    short, _ = reads
    epi = minimizer_index.build_epoched_index(ref, w=8, k=12)
    cfg = EngineConfig(buckets=(96,), max_batch=4, max_delay_s=0.005,
                       filter_k=10, minimizer_w=8, minimizer_k=12)
    with ServeEngine(epi, cfg) as eng:
        r0 = eng.map_all([short.reads[0]])[0]
        assert not r0.cached
        r1 = eng.map_all([short.reads[0]])[0]
        assert r1.cached
        assert (r1.position, r1.distance) == (r0.position, r0.distance)
        assert eng.cache.hits == 1
        epoch0 = epi.epoch
        assert epi.refresh(ref) == epoch0 + 1  # same bases, new epoch
        r2 = eng.map_all([short.reads[0]])[0]
        assert not r2.cached  # old-epoch entry is unreachable
        assert r2.position == r0.position


def test_worker_exception_fails_futures_not_hangs(epi, reads):
    short, _ = reads
    cfg = EngineConfig(buckets=(96,), max_batch=4, max_delay_s=0.005,
                       filter_k=10, minimizer_w=8, minimizer_k=12)
    eng = ServeEngine(epi, cfg)

    def boom(cap):
        raise RuntimeError("executor boom")

    eng._executor = boom
    fut = eng.submit(short.reads[0])
    with pytest.raises(RuntimeError):  # resolved with the error, no hang
        fut.result(timeout=30)
    with pytest.raises(RuntimeError):  # engine refuses new work after death
        eng.submit(short.reads[1])
    eng.close()  # shutdown of a dead engine is still clean
    assert not eng._worker.is_alive()


def test_engine_rejects_mismatched_minimizer_params(ref):
    epi = minimizer_index.build_epoched_index(ref, w=8, k=12)
    with pytest.raises(ValueError, match="minimizer"):
        # engine seeds with the 10/15 defaults; index was built 8/12
        ServeEngine(epi, EngineConfig(buckets=(96,)))


def test_result_cache_unit():
    c = ResultCache(capacity=2)
    a, b, d = (np.full(4, i, np.int8) for i in range(3))
    c.put(a, 0, "A")
    c.put(b, 0, "B")
    assert c.get(a, 0) == "A" and c.get(a, 1) is None  # epoch is part of key
    c.put(d, 0, "D")  # evicts b (a was touched more recently)
    assert c.get(b, 0) is None and c.get(a, 0) == "A"
    assert c.evict_epochs_below(1) == 2 and len(c) == 0
    disabled = ResultCache(capacity=0)
    disabled.put(a, 0, "A")
    assert disabled.get(a, 0) is None
    assert 0.0 <= c.hit_rate <= 1.0


def test_metrics_histogram_and_render():
    m = Metrics()
    h = m.histogram("latency_s")
    for v in (0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    assert h.count == 4
    assert h.quantile(0.5) <= h.quantile(0.99)
    assert h.quantile(0.99) >= 0.05  # p99 lands near the outlier
    m.counter("reads_submitted").inc(3)
    text = m.render()
    assert "reads_submitted 3" in text
    assert "latency_s_p99" in text


def test_prefetcher_propagates_worker_exception():
    def bad():
        yield 0, np.zeros((2, 4), np.int8), np.zeros(2, np.int32)
        raise ValueError("boom")

    pf = pipeline.Prefetcher(bad(), device_put=lambda x: x)
    it = iter(pf)
    assert next(it)[0] == 0
    with pytest.raises(ValueError, match="boom"):
        next(it)
    pf.close()
    assert not pf._t.is_alive()


def test_prefetcher_close_mid_stream():
    def endless():
        i = 0
        while True:
            yield i, np.zeros((1, 4), np.int8), np.ones(1, np.int32)
            i += 1

    with pipeline.Prefetcher(endless(), device_put=lambda x: x, depth=1) as pf:
        assert next(iter(pf))[0] == 0
    deadline = time.monotonic() + 5.0
    while pf._t.is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not pf._t.is_alive()  # close() joined the worker


def test_strip_gids():
    rows = [{"gid": 3, "qname": "anything", "tstart": 7}]
    assert serve_genomics.strip_gids(rows) == [{"qname": "anything",
                                               "tstart": 7}]


def test_offline_online_identical_paf(tmp_path):
    common = ["--ref-len", "4000", "--reads", "10", "--read-len", "100",
              "--batch", "4", "--buckets", "128"]
    p_off, p_on = tmp_path / "off.paf", tmp_path / "on.paf"
    serve_genomics.main(common + ["--out", str(p_off)])
    serve_genomics.main(common + ["--online", "--rate", "500",
                                  "--out", str(p_on)])
    off, on = p_off.read_text(), p_on.read_text()
    assert off == on
    assert off.count("\n") >= 8  # most of the 10 reads mapped
    assert "gid" not in off  # stripped before write_paf


def test_executor_cache_keyed_on_align_backend(epi, reads):
    """Switching align backends must never reuse a stale compiled
    executor: the cache key carries the resolved backend name."""
    short, _ = reads
    cfg = EngineConfig(buckets=(96,), max_batch=4, align_backend="lax",
                       filter_k=10, minimizer_w=8, minimizer_k=12)
    with ServeEngine(epi, cfg) as eng:
        assert eng.align_backend == "lax"
        r_lax = eng.map_all(list(short.reads[:4]))
        keys_lax = set(eng._executors)
    cfg2 = EngineConfig(buckets=(96,), max_batch=4,
                        align_backend="pallas_dc_v2", filter_k=10,
                        minimizer_w=8, minimizer_k=12)
    with ServeEngine(epi, cfg2) as eng2:
        assert eng2.align_backend == "pallas_dc_v2"
        r_pal = eng2.map_all(list(short.reads[:4]))
        assert set(eng2._executors) != keys_lax
    # same reads, same results, different backend underneath
    assert [(r.position, r.distance) for r in r_lax] == \
        [(r.position, r.distance) for r in r_pal]


def test_map_stream_over_prefetcher(epi, reads):
    """genomics.pipeline.map_stream: batches → MapResults via dispatch."""
    short, _ = reads
    idx = epi.index
    batches = pipeline.ReadBatches(list(short.reads), batch=4, cap=96)
    got = {}
    with pipeline.Prefetcher(iter(batches)) as pf:
        for b, res in pipeline.map_stream(idx, pf, backend="lax", p_cap=128,
                                          filter_bits=96, filter_k=12,
                                          minimizer_w=8, minimizer_k=12):
            got[b] = np.asarray(res.position)
    assert sorted(got) == [0, 1, 2]
    pos = np.concatenate([got[b] for b in sorted(got)])[:len(short.true_pos)]
    assert (np.abs(pos - short.true_pos) <= 16).mean() >= 0.7


# a flush's children on the worker's track, in order; its stage spans
# are device windows on a track of their own
FLUSH_CHILDREN = ["device_wait", "fetch", "emit", "trace_replay"]
STAGES = ["seed_filter", "align"]
WORKER_LEAVES = frozenset(FLUSH_CHILDREN) | {"encode", "dispatch",
                                             "worker_wait"}


def test_traced_worker_is_covered_by_leaf_spans(epi, reads):
    """Every flush has the same host children in pipeline order, behind
    its own top-level encode and dispatch; its stage spans are device
    windows that never overlap another flush's; the waits between picks
    are top-level spans; and together the host leaves cover the
    worker's time."""
    from repro.obs import Tracer

    short, _ = reads
    tr = Tracer()
    cfg = EngineConfig(buckets=(96,), max_batch=4, max_delay_s=0.005,
                       filter_k=10, minimizer_w=8, minimizer_k=12,
                       cache_capacity=0)  # every read reaches a flush
    with ServeEngine(epi, cfg, tracer=tr) as eng:
        eng.map_all(list(short.reads[:4]))  # compiles outside the trace
        warm = [s for s in tr.log.spans() if s.name in STAGES]
        tr.log.clear()
        futs = [eng.submit(r) for r in short.reads]  # full and deadline
        for f in futs:
            f.result(timeout=60)
        time.sleep(0.02)  # the worker waits with nothing queued
        for f in [eng.submit(r) for r in short.reads[:3]]:
            f.result(timeout=60)
    spans = tr.log.spans()
    assert not [s for s in spans if s.kind == "instant"]  # no per-read event
    # each executor stage says whether that call compiled it
    assert sorted(s.name for s in warm if s.attrs["compile"]) == [
        "align", "seed_filter"]
    assert not any(s.attrs["compile"] for s in spans if s.name in STAGES)
    flushes = [s for s in spans if s.name == "flush"]
    assert len(flushes) >= 4
    worker = flushes[0].tid
    for f in flushes:
        kids = sorted((s for s in spans if s.parent_id == f.span_id
                       and s.kind == "span" and s.tid == worker),
                      key=lambda s: s.t_start)
        assert [s.name for s in kids] == FLUSH_CHILDREN
        for a, b in zip(kids, kids[1:]):
            assert a.t_end <= b.t_start
        assert f.t_start <= kids[0].t_start and kids[-1].t_end <= f.t_end
        stages = sorted((s for s in spans if s.parent_id == f.span_id
                         and s.name in STAGES), key=lambda s: s.t_start)
        assert [s.name for s in stages] == STAGES
        assert all(s.tid != worker for s in stages)
        # the results are ready when the worker's wait for them ends
        assert stages[-1].t_end <= kids[0].t_end
        waits = [s for s in spans
                 if s.parent_id == f.span_id and s.name == "enqueue_wait"]
        assert len(waits) == f.attrs["batch"]
    # device windows of one engine never overlap, across flushes too
    windows = sorted((s.t_start, s.t_end) for s in spans if s.name in STAGES)
    assert len(windows) == 2 * len(flushes)
    for a, b in zip(windows, windows[1:]):
        assert a[0] <= a[1] <= b[0]
    # the worker's top-level spans follow one another: each flush is
    # dispatched by its own encode + dispatch and finished by a later
    # pick or an idle queue
    top = sorted((s for s in spans if s.parent_id is None
                  and s.kind == "span" and s.tid == worker),
                 key=lambda s: s.t_start)
    names = [s.name for s in top]
    assert names.count("dispatch") == names.count("encode") == len(flushes)
    assert names.count("worker_wait") >= len(flushes) - 1
    for a, b in zip(top, top[1:]):
        assert a.t_end <= b.t_start
    # host leaf spans cover the worker from its first pick to the last
    # flush's end; the margin leaves room for a loaded test host (a
    # traced v5e run reads over 97%, PERF.md)
    t0 = min(s.t_start for s in top if s.name == "encode")
    t1 = max(f.t_end for f in flushes)
    covered, reach = 0.0, t0
    for a, b in sorted((s.t_start, s.t_end) for s in spans
                       if s.name in WORKER_LEAVES and s.kind == "span"
                       and s.tid == worker):
        a, b = max(a, reach), min(b, t1)
        if b > a:
            covered, reach = covered + (b - a), b
    assert covered >= 0.8 * (t1 - t0)


def _rung_reference(epi, eng, reads_by_cap):
    """What `mapper.map_batch` gives for each rung's reads, per read."""
    from repro.core import mapper
    from repro.genomics import encode

    c = eng.config
    out = {}
    for cap, rs in reads_by_cap.items():
        arr, lens = encode.batch_reads(rs, cap)
        res = mapper.map_batch(
            epi.index, arr, lens, cfg=c.genasm, p_cap=cap,
            filter_bits=min(c.filter_bits, cap), filter_k=c.filter_k,
            max_candidates=c.max_candidates, minimizer_w=c.minimizer_w,
            minimizer_k=c.minimizer_k, backend=eng.align_backend)
        out[cap] = [tuple(np.asarray(f)[i] for f in
                          (res.position, res.distance, res.ops, res.n_ops))
                    for i in range(len(rs))]
    return out


def test_pipelined_results_equal_map_batch(epi, reads):
    """The one-chip engine keeps a flush in flight and still answers
    bit for bit what `map_batch` does, over two rungs, with full and
    deadline flushes, and traces each rung's stages once."""
    short, long = reads
    cfg = EngineConfig(buckets=(96, 192), max_batch=4, max_delay_s=0.005,
                       filter_k=10, minimizer_w=8, minimizer_k=12,
                       cache_capacity=0)
    with ServeEngine(epi, cfg) as eng:
        with eng._cv:  # the whole backlog is queued before the first pick
            futs = [eng.submit(r) for r in list(short.reads)
                    + list(long.reads)]
        got = [f.result(timeout=60) for f in futs]
        snap = eng.metrics.snapshot()
        counts = dict(eng.trace_counts)
        # tracing off: no stage watcher thread, no stamps
        assert eng._watcher is None
        assert all(p.windows is None for p in [eng._pending] if p)
        assert "serve-engine-stages" not in {
            t.name for t in threading.enumerate()}
        ref = _rung_reference(epi, eng, {96: list(short.reads),
                                         192: list(long.reads)})
    want = ref[96] + ref[192]
    for g, (pos, dist, ops, n_ops) in zip(got, want):
        assert (g.position, g.distance, g.n_ops) == (pos, dist, n_ops)
        assert g.ops.dtype == ops.dtype and np.array_equal(g.ops, ops)
    # 96: two full flushes and a deadline flush of 2; 192: one of 2
    assert snap["batches_flushed_cap96"] == 3
    assert snap["batches_flushed_cap192"] == 1
    assert snap["batch_occupancy_mean"] < 1.0
    assert snap["flushes_overlapped"] >= 1
    assert counts == {(96, "seed_filter"): 1, (96, "align"): 1,
                      (192, "seed_filter"): 1, (192, "align"): 1}


class _Proxy:
    """Stands in for an executor; ``on_call`` runs before each call."""

    def __init__(self, fn, on_call):
        self.fn, self.on_call = fn, on_call

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, *args):
        self.on_call()
        return self.fn(*args)


def _instrument(eng):
    """Count the flushes dispatched to the executor and not yet fetched."""
    seen = {"open": 0, "open_at_start": []}
    make, fetch = eng._executor, eng._fetch

    def started():
        seen["open_at_start"].append(seen["open"])
        seen["open"] += 1

    def fetched(*a, **k):
        seen["open"] -= 1
        return fetch(*a, **k)

    eng._executor = lambda *a, **k: _Proxy(make(*a, **k), started)
    eng._fetch = fetched
    return seen


def test_pipelined_keeps_one_flush_in_flight(epi, reads):
    """Under a backlog each flush is dispatched while exactly one other
    is in flight, never two."""
    short, _ = reads
    cfg = EngineConfig(buckets=(96,), max_batch=2, max_delay_s=0.005,
                       filter_k=10, minimizer_w=8, minimizer_k=12,
                       cache_capacity=0)
    with ServeEngine(epi, cfg) as eng:
        eng.map_all(list(short.reads[:2]))  # builds and compiles
        seen = _instrument(eng)
        with eng._cv:  # five full flushes queued before the first pick
            futs = [eng.submit(r) for r in short.reads]
        for f in futs:
            f.result(timeout=60)
        eng.drain(timeout=60)
        snap = eng.metrics.snapshot()
    assert seen["open_at_start"] == [0, 1, 1, 1, 1]
    assert seen["open"] == 0
    assert snap["flushes_overlapped"] == 4


def test_lone_read_resolves_through_idle_finish(epi, reads):
    """A read with nothing behind it is dispatched at its deadline and
    finished because the queue is idle, not left in flight."""
    short, _ = reads
    clk = _FakeClock()
    cfg = EngineConfig(buckets=(96,), max_batch=8, max_delay_s=0.03,
                       filter_k=10, minimizer_w=8, minimizer_k=12)
    with ServeEngine(epi, cfg, clock=clk) as eng:
        fut = eng.submit(short.reads[0])
        time.sleep(0.15)
        assert not fut.done()  # frozen before its deadline
        clk.advance(1.0)
        res = fut.result(timeout=30)  # no later pick or close finishes it
        assert eng._pending is None
        snap = eng.metrics.snapshot()
    assert res.bucket_cap == 96 and not res.cached
    assert snap["batches_flushed"] == 1
    assert "flushes_overlapped" not in snap


@pytest.mark.parametrize("stage", ["start", "finish"])
def test_pipelined_failure_fails_picked_and_inflight(epi, reads, stage):
    """An executor failure while one flush is in flight and the next is
    picked fails both flushes' futures and the queued ones, no hang."""
    short, _ = reads
    cfg = EngineConfig(buckets=(96,), max_batch=2, max_delay_s=0.005,
                       filter_k=10, minimizer_w=8, minimizer_k=12,
                       cache_capacity=0)
    eng = ServeEngine(epi, cfg)
    eng.map_all(list(short.reads[:2]))  # builds and compiles
    (ex,) = eng.executors
    # the backlog's second dispatch raises, or its first fetch, which
    # comes once the second flush is dispatched
    calls = {"n": 1 if stage == "finish" else 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError(f"{stage} boom")

    if stage == "start":
        eng._executor = lambda *a, **k: _Proxy(ex, flaky)
    else:
        fetch = eng._fetch

        def flaky_fetch(*a, **k):
            flaky()
            return fetch(*a, **k)
        eng._fetch = flaky_fetch
    with eng._cv:  # three full flushes queued before the first pick
        futs = [eng.submit(r) for r in short.reads[:6]]
    for f in futs:
        with pytest.raises(RuntimeError, match="boom"):
            f.result(timeout=30)
    with pytest.raises(RuntimeError):
        eng.submit(short.reads[7])
    eng.close()
    assert not eng._worker.is_alive()
