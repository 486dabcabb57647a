"""Async micro-batching engine for online read-mapping (DESIGN.md §8).

Reads arrive continuously via ``submit() -> Future``; the engine admits
them into per-bucket queues and a background worker flushes a bucket when
it reaches ``max_batch`` *or* its oldest read has waited ``max_delay_s``
(the classic throughput/latency micro-batching tradeoff).

Two wastes of the offline driver are removed here:

* **Padding waste** — instead of padding every read to one global cap,
  reads are routed to the smallest rung of a *length-bucket ladder*
  (default 160/320/640/1280) that holds them, so a 150 bp Illumina read
  stops paying 1280-cap long-read padding.  `metrics` tracks the padded
  bases actually paid per bucket (benchmarks/serve_engine.py quantifies
  the win vs single-cap batching).
* **Recompile waste** — `mapper.map_batch` is shape-specialized, so each
  ``(bucket_cap, align_backend, config)`` triple jits exactly once into
  an *executor cache*; partial flushes are padded up to ``max_batch``
  rows to keep one trace per bucket (``trace_counts`` makes this
  assertable in tests).  Alignment inside the executor flows through
  `repro.align.align_batch`, so the engine serves any registered
  backend (``lax``, ``pallas_dc``, ``pallas_dc_v2``, …) unchanged.

Results are memoized in an LRU keyed on ``(read digest, index epoch
token)`` (`cache.py`) — a scalar epoch for single-device indexes, the
``(layout, epoch vector)`` token for sharded ones; refreshing the
reference bumps it and invalidates the lot.  The engine is
mode-agnostic: the offline WorkQueue path and the online Poisson path
in `launch/serve_genomics.py` both sit on the same
``submit()``/``drain()`` surface, which is what makes their PAF outputs
bit-identical.  With ``num_shards > 1`` the bucket executors become
`repro.shard` scatter/merge/align pipelines (DESIGN.md §11) with
byte-identical output.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mapper
from repro.core.genasm import GenASMConfig
from repro.core.minimizer_index import EpochedIndex, ReferenceIndex
from repro.genomics import encode
from repro.obs.trace import NULL_TRACER, Tracer

from .cache import ResultCache, read_digest
from .metrics import Metrics


@dataclass(frozen=True)
class EngineConfig:
    """Micro-batcher policy + the static half of the mapper signature.

    ``buckets`` are pattern caps (must be multiples of 32 for the
    bitvector layout, DESIGN.md §7); reads longer than the top rung are
    trimmed to it, matching `encode.batch_reads`.  ``filter_bits`` is
    clamped per bucket to the bucket cap so narrow buckets stay legal.
    ``align_backend`` names a `repro.align` registry entry ("auto"
    resolves per platform at engine construction); it is part of the
    executor-cache key, so switching backends never reuses a stale
    compiled executor.

    ``workload`` selects what a bucket executor compiles: ``"linear"``
    (`core/mapper.map_batch` against an `EpochedIndex`) or ``"graph"``
    (`repro.graph.mapper.map_batch` against an `EpochedGraphIndex`,
    results carrying the node path for GAF).  It is part of the
    executor-cache key; linear backend names resolve to their graph
    twins under the graph workload (``lax`` → ``graph_lax``, …).

    ``num_shards > 1`` serves through `repro.shard`: the engine wraps
    the index into its epoch-vector-stamped sharded form, bucket
    executors become scatter/merge/align pipelines (``shard_map`` over
    a shard mesh when enough devices exist, stacked ``vmap``
    otherwise), and the result cache keys on the (layout, epoch
    vector) token instead of a scalar epoch.  ``shard_candidates`` is
    each shard's per-read candidate budget (None = ``max_candidates``,
    the identity-preserving default; throughput deployments set
    ``max_candidates // num_shards`` to strong-scale the filter).
    PAF/GAF output is byte-identical to ``num_shards=1`` as long as the
    single-device winner ranks within ``shard_candidates`` by votes in
    its owning shard — automatic for real reads at the default budget;
    see the `repro.shard.mapper` caveat before shrinking it on highly
    repetitive references.

    One chip's linear workload always keeps one flush in flight: the
    worker dispatches flush *i+1*'s two stages through a non-blocking
    executor call before it fetches and emits flush *i*
    (double buffering), so the device never waits on the host between
    flushes.  ``align_sharded`` (sharded serving only) splits the
    winning-window align stage over the same shard mesh as the scatter
    stage; ``pipelined`` is sharded serving's opt-in to the same
    double-buffered worker, overlapping batch *i*'s align with batch
    *i+1*'s scatter.  Both are bitwise-neutral on output and part of
    the executor-cache key.  The graph workload on one chip flushes
    one batch at a time: its executor syncs mid-flush to pick a tile
    rung.
    """

    buckets: tuple[int, ...] = (160, 320, 640, 1280)
    max_batch: int = 32
    max_delay_s: float = 0.005
    genasm: GenASMConfig = GenASMConfig()
    align_backend: str = "auto"
    workload: str = "linear"
    filter_bits: int = 128
    filter_k: int = 12
    max_candidates: int = 4
    num_shards: int = 1
    shard_candidates: int | None = None  # None = max_candidates per shard
    # defaults match build_reference_index/build_epoched_index and
    # mapper.map_batch, so all-defaults construction is consistent
    minimizer_w: int = 10
    minimizer_k: int = 15
    cache_capacity: int = 4096  # 0 disables the result cache
    # graph workload: q-gram tile screen before the BitAlign-DC filter
    # (bitwise-neutral on output; off only for A/B measurement)
    graph_prefilter: bool = True
    # graph workload: the most bytes the graph index may hold on the
    # device, all shards together (what the graph_index_device_bytes
    # gauge reads); the engine refuses a larger index at start.  None:
    # no limit
    graph_index_max_bytes: int | None = None
    # sharded serving: mesh-split align stage / double-buffered flushes
    align_sharded: bool = False
    pipelined: bool = False

    def __post_init__(self):
        if not self.buckets:
            raise ValueError("need at least one bucket cap")
        if any(c % 32 or c <= 0 for c in self.buckets):
            raise ValueError(f"bucket caps must be positive multiples of 32, "
                             f"got {self.buckets}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.workload not in ("linear", "graph"):
            raise ValueError(f"workload must be 'linear' or 'graph', got "
                             f"{self.workload!r}")
        if self.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got "
                             f"{self.num_shards}")
        if self.shard_candidates is not None and self.shard_candidates < 1:
            raise ValueError(f"shard_candidates must be >= 1, got "
                             f"{self.shard_candidates}")
        if (self.graph_index_max_bytes is not None
                and self.graph_index_max_bytes < 1):
            raise ValueError(f"graph_index_max_bytes must be >= 1, got "
                             f"{self.graph_index_max_bytes}")
        if (self.align_sharded or self.pipelined) and self.num_shards < 2:
            raise ValueError(
                "align_sharded/pipelined serve through the repro.shard "
                "executors; they need num_shards > 1")
        object.__setattr__(self, "buckets", tuple(sorted(set(self.buckets))))

    def bucket_for(self, length: int) -> int:
        """Smallest rung holding ``length`` (top rung trims longer reads)."""
        for cap in self.buckets:
            if length <= cap:
                return cap
        return self.buckets[-1]


class ServeResult(NamedTuple):
    """Per-read mapping outcome delivered through the submit() future."""

    position: int  # reference start (-1 if unmapped)
    distance: int  # edit distance (-1 if unmapped)
    ops: np.ndarray  # packed CIGAR ops
    n_ops: int
    read_len: int
    bucket_cap: int
    cached: bool
    latency_s: float
    path: np.ndarray | None = None  # graph workload: node ids per op (-1=I)


@dataclass
class _Request:
    read: np.ndarray
    length: int
    bucket: int
    future: Future
    digest: bytes | None = None  # computed once in submit(), reused by put()
    t_submit: float = field(default_factory=time.monotonic)


class _HostResult(NamedTuple):
    """A flush's results on the host: what `ServeEngine._deliver` reads."""

    position: np.ndarray
    distance: np.ndarray
    ops: np.ndarray
    n_ops: np.ndarray
    path: np.ndarray | None


class _PendingFlush(NamedTuple):
    """One dispatched-but-unmaterialized flush."""

    cap: int
    reqs: list
    fn: object  # the executor that dispatched it
    res: object  # its result tree, device leaves
    pending: object  # a sharded executor's PendingBatch; None on one chip
    epoch: object
    lens: np.ndarray
    t_flush: float
    windows: tuple | None  # _StageWatcher.watch's (times, ready)


class _StageWatcher:
    """Times each stage a flush dispatched on the device.

    One thread blocks on the stage outputs in dispatch order, which is
    the order the device runs them, and stamps each as it becomes ready.
    A stage's window runs from its dispatch or the completion of the
    stage before it, whichever is later, to its own completion: the
    windows never overlap and add up to the device's time per stage,
    with the gaps before each stage's first op and the thread's wake-up
    lag in them.  The engine starts one at its first dispatch that is
    traced or feeds the roofline counters: otherwise there is no thread
    and no stamp.
    """

    def __init__(self) -> None:
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, name="serve-engine-stages", daemon=True)
        self._thread.start()

    def watch(self, stages) -> tuple[list, threading.Event]:
        """Queue ``(stage, t_dispatched, output, attrs)`` tuples →
        ``(times, ready)``: the event is set once ``times`` holds each
        stage's ``(stage, t0, t1, attrs)`` window."""
        times: list = []
        ready = threading.Event()
        self._q.put((stages, times, ready))
        return times, ready

    def close(self) -> None:
        """Stop the thread once it has timed what was queued."""
        self._q.put(None)
        self._thread.join(timeout=10.0)

    def _run(self) -> None:
        t_free = float("-inf")  # when the last stage left the device
        while (item := self._q.get()) is not None:
            stages, times, ready = item
            out = None
            try:
                for name, t_dispatch, out, attrs in stages:
                    jax.block_until_ready(out)
                    t_done = time.monotonic()
                    times.append((name, max(t_dispatch, t_free), t_done,
                                  attrs))
                    t_free = t_done
            except Exception:  # noqa: BLE001 — the worker's wait raises it
                pass  # a failed stage leaves its flush without windows
            finally:
                ready.set()
            del item, stages, out  # the worker frees the device buffers


class ServeEngine:
    """Admission queue + per-bucket micro-batcher over `mapper.map_batch`."""

    def __init__(self, index,
                 config: EngineConfig = EngineConfig(),
                 metrics: Metrics | None = None,
                 tracer: Tracer | None = None,
                 roofline=None,
                 clock=time.monotonic):
        self.config = config
        # NULL_TRACER's span()/add()/event() are near-free no-ops, so the
        # untraced hot path stays untaxed (ISSUE: <3% overhead traced)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # optional repro.obs.roofline.RooflineManager: per-flush analytic
        # kernel counters keyed by this engine's align dispatch sites
        self.roofline = roofline
        # every deadline/latency decision reads this clock, so tests can
        # inject a fake monotonic clock and assert flush policy without
        # real sleeps (the worker still polls it every <=50 ms of real
        # time while reads wait)
        self._clock = clock

        def check_minimizer(kw):
            if (kw["w"], kw["k"]) != (config.minimizer_w, config.minimizer_k):
                raise ValueError(
                    f"index built with minimizer w={kw['w']}/k={kw['k']} but "
                    f"engine seeds with w={config.minimizer_w}/"
                    f"k={config.minimizer_k}; hashes would never match")

        if config.workload == "graph":
            from repro.graph.index import EpochedGraphIndex, GraphIndex

            if isinstance(index, GraphIndex):
                index = EpochedGraphIndex(index)
            elif not isinstance(index, EpochedGraphIndex) and not (
                    config.num_shards > 1 and self._is_sharded_graph(index)):
                raise TypeError(
                    f"graph workload needs a GraphIndex/EpochedGraphIndex, "
                    f"got {type(index).__name__}")
            if isinstance(index, EpochedGraphIndex):
                check_minimizer(index._build_kw)
            if config.num_shards > 1:
                index = self._shard_graph_index(index)
        elif config.num_shards > 1:
            index = self._shard_linear_index(index, check_minimizer)
        elif not isinstance(index, EpochedIndex):
            # a bare ReferenceIndex carries no build params, so the engine
            # assumes it was built with config.minimizer_w/k (prefer
            # build_epoched_index, which records the actual params and is
            # validated below); the wrap keeps refresh() consistent
            index = EpochedIndex(index, w=config.minimizer_w,
                                 k=config.minimizer_k)
        else:
            check_minimizer(index._build_kw)
        self.index = index
        # resolve "auto" once: the executor-cache key and every flush use
        # the same concrete backend for the engine's whole lifetime
        from repro import align as align_dispatch

        if config.workload == "graph":
            from repro.graph.mapper import graph_backend_name

            self.align_backend = graph_backend_name(config.align_backend)
        else:
            self.align_backend = align_dispatch.resolve_backend(
                config.align_backend).name
        self.metrics = metrics or Metrics()
        if config.workload == "graph":
            self._graph_gauges(self.index.index)
        self.cache = ResultCache(config.cache_capacity)
        self._queues: dict[int, list[_Request]] = {c: [] for c in config.buckets}
        self._executors: dict[tuple, object] = {}
        self.trace_counts: dict[int, int] = {}
        self._cv = threading.Condition()
        self._inflight = 0
        # one chip's linear executor dispatches both of its stages with
        # no host sync, so the worker keeps one flush in flight behind
        # the running one; sharded serving opts in; the graph executor
        # syncs mid-flush and flushes one batch at a time
        self._pipelined = config.pipelined or (
            config.workload == "linear" and config.num_shards == 1)
        self._pending: _PendingFlush | None = None  # pipelined: one in flight
        self._watcher: _StageWatcher | None = None  # one chip, timed only
        self._closed = False
        self._error: BaseException | None = None
        self._worker = threading.Thread(
            target=self._run, name="serve-engine", daemon=True)
        self._worker.start()

    def _graph_gauges(self, gidx) -> None:
        """The graph index's size: nodes, and the bytes it holds on the
        device (a sharded index counts every shard's), held to
        ``graph_index_max_bytes``."""
        nbytes = sum(int(a.nbytes) for a in gidx.arrays)
        limit = self.config.graph_index_max_bytes
        if limit is not None and nbytes > limit:
            raise ValueError(
                f"the graph index holds {nbytes} bytes on the device, over "
                f"graph_index_max_bytes={limit}")
        self.metrics.gauge("graph_nodes").set(gidx.n_nodes)
        self.metrics.gauge("graph_index_device_bytes").set(nbytes)

    # ------------------------------------------------------------ sharding --
    def _shard_halo(self) -> int:
        """Smallest halo covering every bucket's mapping geometry."""
        from repro import shard

        c = self.config
        cap = max(c.buckets)
        return max(shard.DEFAULT_HALO, shard.required_halo(
            p_cap=cap, filter_bits=min(c.filter_bits, cap),
            filter_k=c.filter_k, t_cap=cap + 2 * c.genasm.w))

    @staticmethod
    def _is_sharded_graph(index) -> bool:
        from repro.shard import EpochedShardedGraphIndex, ShardedGraphIndex

        return isinstance(index, (EpochedShardedGraphIndex,
                                  ShardedGraphIndex))

    def _shard_linear_index(self, index, check_minimizer):
        """Wrap/convert a linear index for ``num_shards > 1`` serving."""
        from repro import shard

        c = self.config
        if isinstance(index, shard.EpochedShardedIndex):
            esi = index
        elif isinstance(index, shard.ShardedIndex):
            raise TypeError(
                "sharded serving needs an EpochedShardedIndex (it carries "
                "the host reference for failover re-materialization); got "
                "a bare ShardedIndex — build via shard.from_epoched")
        else:
            if isinstance(index, EpochedIndex):
                check_minimizer(index._build_kw)
            index_or_epi = index if isinstance(index, EpochedIndex) else \
                EpochedIndex(index, w=c.minimizer_w, k=c.minimizer_k)
            esi = shard.from_epoched(index_or_epi, c.num_shards,
                                     halo=self._shard_halo())
        if esi.index.num_shards != c.num_shards:
            raise ValueError(
                f"index sharded {esi.index.num_shards} ways but config "
                f"asks for num_shards={c.num_shards}")
        if (esi.index.minimizer_w, esi.index.minimizer_k) != \
                (c.minimizer_w, c.minimizer_k):
            raise ValueError(
                f"sharded index built with minimizer "
                f"w={esi.index.minimizer_w}/k={esi.index.minimizer_k} but "
                f"engine seeds with w={c.minimizer_w}/k={c.minimizer_k}")
        return esi

    def _shard_graph_index(self, index):
        """Wrap/convert a graph index for ``num_shards > 1`` serving."""
        from repro import shard
        from repro.graph.index import EpochedGraphIndex

        c = self.config
        if isinstance(index, shard.EpochedShardedGraphIndex):
            esi = index
        elif isinstance(index, shard.ShardedGraphIndex):
            raise TypeError(
                "sharded graph serving needs an EpochedShardedGraphIndex "
                "— build via shard.from_epoched_graph")
        else:
            assert isinstance(index, EpochedGraphIndex)
            esi = shard.from_epoched_graph(index, c.num_shards,
                                           halo=self._shard_halo())
        if esi.index.num_shards != c.num_shards:
            raise ValueError(
                f"index sharded {esi.index.num_shards} ways but config "
                f"asks for num_shards={c.num_shards}")
        if (esi.index.minimizer_w, esi.index.minimizer_k) != \
                (c.minimizer_w, c.minimizer_k):
            raise ValueError(
                f"sharded graph index built with minimizer "
                f"w={esi.index.minimizer_w}/k={esi.index.minimizer_k} but "
                f"engine seeds with w={c.minimizer_w}/k={c.minimizer_k}")
        return esi

    # ----------------------------------------------------------- client API --
    def submit(self, read: np.ndarray) -> Future:
        """Admit one read; the future resolves to a ``ServeResult``."""
        read = np.ascontiguousarray(read, dtype=np.int8)
        fut: Future = Future()
        t0 = self._clock()
        with self._cv:  # a dead engine answers nothing, not even cache hits
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._error is not None:
                raise RuntimeError("engine worker died") from self._error
        _, epoch = self.index.current()
        # hit/miss accounting lives in the cache itself (cache.hit_rate),
        # not duplicated into Metrics
        digest = read_digest(read) if self.cache.capacity else None
        hit = self.cache.get(read, epoch, digest=digest)
        self.metrics.counter("reads_submitted").inc()
        if hit is not None:
            fut.set_result(hit._replace(
                cached=True, ops=hit.ops.copy(),  # callers own their arrays
                path=None if hit.path is None else hit.path.copy(),
                latency_s=self._clock() - t0))
            return fut
        req = _Request(read=read, length=len(read),
                       bucket=self.config.bucket_for(len(read)), future=fut,
                       digest=digest, t_submit=t0)
        with self._cv:
            # re-checked under the enqueue lock: a request can never land
            # after the worker has observed "closed and empty" and left
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._error is not None:
                raise RuntimeError("engine worker died") from self._error
            self._queues[req.bucket].append(req)
            self._inflight += 1
            self.metrics.gauge("queue_depth").set(
                sum(len(q) for q in self._queues.values()))
            self._cv.notify_all()  # the worker may not be the FIFO waiter
        return fut

    def map_all(self, reads: Sequence[np.ndarray]) -> list[ServeResult]:
        """Submit a read list and gather results in submission order."""
        futs = [self.submit(r) for r in reads]
        return [f.result() for f in futs]

    def drain(self, timeout: float | None = None) -> None:
        """Block until every admitted read has a result."""
        deadline = None if timeout is None else self._clock() + timeout
        with self._cv:
            while self._inflight > 0 and self._error is None:
                wait = (None if deadline is None
                        else max(deadline - self._clock(), 0.0))
                if wait == 0.0:
                    raise TimeoutError(
                        f"drain timed out with {self._inflight} in flight")
                self._cv.wait(timeout=0.05 if wait is None else min(wait, 0.05))
        if self._error is not None:
            raise RuntimeError("engine worker died") from self._error

    def close(self) -> None:
        """Drain, then stop the worker (idempotent, even after worker death)."""
        with self._cv:
            if self._closed:
                return
        try:
            self.drain()
        except RuntimeError:
            pass  # worker already dead: nothing left to drain, still shut down
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._worker.join(timeout=10.0)
        if self._watcher is not None:
            self._watcher.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ----------------------------------------------------- executor cache ----
    def _executor_key(self, cap: int, geom=None) -> tuple:
        c = self.config
        return (cap, c.workload, self.align_backend, c.genasm,
                min(c.filter_bits, cap), c.filter_k, c.max_candidates,
                c.num_shards, c.shard_candidates,
                c.minimizer_w, c.minimizer_k, c.max_batch, geom,
                c.graph_prefilter, c.align_sharded, c.pipelined)

    def _count_trace(self, cap: int, stage=None) -> None:
        """Executor-body hook: runs at trace time only → counts retraces.

        Every executor passes a stage key — linear ``("seed_filter",)``
        / ``("align",)``, sharded ``("scatter",)`` / ``("align",)``,
        graph ``("prefilter",)``, ``(n_cap,)`` per tile-count rung, and
        ``("align",)`` — counted as ``(cap, *stage)``, so the engine's
        (read-length rung, tile-count rung) bucket ladder is assertable
        as one trace per pair."""
        key = cap if stage is None else (cap,) + tuple(stage)
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1

    def _executor(self, cap: int, geom=None, sharded_index=None):
        """One compiled ``map_batch`` per (bucket_cap, workload, backend,
        config) — built lazily.  ``geom`` is the index geometry *at
        flush time* — the graph index's ``geometry`` (tile stride,
        margin, window), or a sharded
        index's ``layout_key`` — baked into the compiled closure, so it
        rides in the key: a refresh() that re-tiles the graph (or
        re-partitions the shards) gets a fresh executor instead of
        silently mis-gathering through a stale one.  ``sharded_index``
        is the *same snapshot* ``_execute`` took from ``current()`` —
        re-reading ``self.index`` here would race a concurrent
        ``refresh()`` and bake the new geometry under the old key."""
        key = self._executor_key(cap, geom)
        fn = self._executors.get(key)
        if fn is None:
            c = self.config
            fbits = min(c.filter_bits, cap)
            backend = self.align_backend
            mode = os.environ.get("REPRO_ALIGN_AUTOTUNE")
            if mode in ("1", "model"):
                # tune eagerly before jitting: under the executor's trace
                # align_batch only *consults* the block cache (it cannot
                # time candidates on tracers)
                from repro import align as align_dispatch

                if align_dispatch.get_backend(backend).uses_pallas:
                    if mode == "model":
                        align_dispatch.model_seed(backend, cap, c.genasm.k,
                                                  batch=c.max_batch)
                    else:
                        align_dispatch.autotune(backend, cap, c.genasm.k,
                                                batch=c.max_batch,
                                                cfg=c.genasm)

            n_cand = c.shard_candidates or c.max_candidates
            if c.num_shards > 1 and c.workload == "graph":
                from repro.shard import ShardedGraphMapExecutor

                fn = ShardedGraphMapExecutor(
                    sharded_index, cfg=c.genasm, p_cap=cap,
                    filter_bits=fbits, filter_k=c.filter_k,
                    shard_candidates=n_cand, backend=backend,
                    prefilter=c.graph_prefilter,
                    align_sharded=c.align_sharded,
                    trace_hook=partial(self._count_trace, cap))
            elif c.num_shards > 1:
                from repro.shard import ShardedMapExecutor

                fn = ShardedMapExecutor(
                    sharded_index, cfg=c.genasm, p_cap=cap,
                    filter_bits=fbits, filter_k=c.filter_k,
                    shard_candidates=n_cand, backend=backend,
                    align_sharded=c.align_sharded,
                    trace_hook=partial(self._count_trace, cap))
            elif c.workload == "graph":
                from repro.graph.mapper import GraphMapExecutor

                # host-orchestrated: the executor jits its own stages
                # (one prefilter + align trace per cap, one candidate
                # stage per tile-count rung — the graph bucket ladder)
                fn = GraphMapExecutor(
                    geometry=geom, cfg=c.genasm, p_cap=cap,
                    filter_bits=fbits, filter_k=c.filter_k,
                    max_candidates=c.max_candidates,
                    minimizer_w=c.minimizer_w, minimizer_k=c.minimizer_k,
                    backend=backend, prefilter=c.graph_prefilter,
                    trace_hook=partial(self._count_trace, cap))
            else:
                # two-stage executor: same math as one fused map_batch
                # jit, called without blocking by the double-buffered
                # worker; the stage outputs it exposes (last_stages) time
                # the seed_filter/align boundary on the device
                fn = mapper.LinearMapExecutor(
                    cfg=c.genasm, p_cap=cap, filter_bits=fbits,
                    filter_k=c.filter_k, max_candidates=c.max_candidates,
                    minimizer_w=c.minimizer_w, minimizer_k=c.minimizer_k,
                    backend=backend, blocking=False,
                    trace_hook=partial(self._count_trace, cap))
            self._executors[key] = fn
        return fn

    @property
    def n_executors(self) -> int:
        """Number of compiled bucket executors currently cached."""
        return len(self._executors)

    @property
    def executors(self) -> tuple:
        """The cached bucket executors (e.g. to check a sharded one's
        ``spmd``)."""
        return tuple(self._executors.values())

    # ------------------------------------------------------------- worker ----
    def _flush_candidate(self, now: float) -> tuple[int, list[_Request]] | None:
        """Pick a bucket to flush: the most-overdue one, else any full one.

        Deadline beats fullness — sustained traffic keeping one bucket
        full must not starve another bucket's ``max_delay_s`` bound (the
        full bucket flushes on the very next worker cycle anyway).

        Caller holds the lock.  Returns (cap, requests) with the requests
        removed from the queue, or None if no bucket is ready.
        """
        overdue_cap, overdue_age = None, 0.0
        for cap, q in self._queues.items():
            if not q:
                continue
            age = now - q[0].t_submit
            if age >= self.config.max_delay_s and age >= overdue_age:
                overdue_cap, overdue_age = cap, age
        if overdue_cap is None:
            full = [c for c, q in self._queues.items()
                    if len(q) >= self.config.max_batch]
            if not full:
                return None
            overdue_cap = full[0]
        q = self._queues[overdue_cap]
        batch, self._queues[overdue_cap] = q[:self.config.max_batch], \
            q[self.config.max_batch:]
        return overdue_cap, batch

    def _next_deadline(self, now: float) -> float | None:
        ages = [now - q[0].t_submit for q in self._queues.values() if q]
        if not ages:
            return None
        return max(self.config.max_delay_s - max(ages), 0.0)

    def _run(self) -> None:
        picked: tuple[int, list[_Request]] | None = None
        tr = self.tracer
        t_idle = None  # end of the worker's last span: a worker_wait starts
        try:
            while True:
                action = "stop"
                with self._cv:
                    while True:
                        if self._closed and not any(self._queues.values()):
                            action = "stop"
                            break
                        now = self._clock()
                        picked = self._flush_candidate(now)
                        if picked is not None:
                            action = "exec"
                            break
                        if self._pending is not None:
                            # idle queue: materialize the in-flight batch
                            # rather than sitting on its futures
                            action = "finish"
                            break
                        wait = self._next_deadline(now)
                        # cap the sleep so an injected fake clock (tests)
                        # is re-polled every <=50 ms of real time
                        self._cv.wait(timeout=0.05 if wait is None
                                      else min(wait, 0.05))
                    self.metrics.gauge("queue_depth").set(
                        sum(len(q) for q in self._queues.values()))
                t_pick = time.monotonic()
                if tr.enabled and t_idle is not None:
                    # the stretch from the last flush's end to this pick:
                    # the inflight release, the lock, the queue scan and
                    # any wait for a bucket to become ready
                    tr.add("worker_wait", t_idle, t_pick)
                if action == "stop":
                    self._release(self._finish_pending()[0])
                    return
                if action == "finish":
                    done, t_idle = self._finish_pending(t_pick)
                else:
                    cap, reqs = picked  # compute outside the lock
                    if self._pipelined:
                        done, t_idle = self._execute_pipelined(cap, reqs,
                                                               t_pick)
                    else:
                        done, t_idle = self._execute(cap, reqs, t_pick)
                    picked = None
                self._release(done)
        except BaseException as e:  # noqa: BLE001 — worker must not die silently
            with self._cv:
                self._error = e
                failed = [r for q in self._queues.values() for r in q]
                if picked is not None:  # the batch mid-execute fails too
                    failed += picked[1]
                if self._pending is not None:  # and the dispatched one
                    failed += self._pending.reqs
                    self._pending = None
                for q in self._queues.values():
                    q.clear()
                for r in failed:
                    if not r.future.done():
                        r.future.set_exception(e)
                self._inflight = 0
                self._cv.notify_all()

    def _release(self, n: int) -> None:
        """Count ``n`` delivered reads out of flight; wakes drain()."""
        if n:
            with self._cv:
                self._inflight -= n
                self._cv.notify_all()

    def _execute_pipelined(self, cap: int, reqs: list[_Request],
                           t_pick: float) -> tuple[int, float]:
        """Dispatch a flush without materializing it; finish the previous.

        Double buffering, one flush deep: flush *i+1*'s encode and
        dispatch (seed_filter then align on one chip; scatter, device
        merge and align sharded) queue it on the device behind flush
        *i*, which is then waited for, fetched and emitted while *i+1*
        runs.  Returns ``(reads delivered, end of the worker's last
        span)``: the previous flush's reads.  Encode starts at
        ``t_pick``, where ``worker_wait`` ended, and each span of the
        worker starts where the one before it ended.
        """
        c, tr = self.config, self.tracer
        prev, self._pending = self._pending, None
        try:
            t_flush = self._clock()
            # no flush span yet (it opens when the flush is finished), so
            # encode and dispatch are top-level spans of the worker
            arr, enc = self._encode(cap, reqs, t_start=t_pick)
            with tr.span("dispatch", t_start=enc.t_end, bucket_cap=cap,
                         overlapped=prev is not None) as disp:
                index, epoch = self.index.current()
                lens = self._lengths(cap, reqs)
                pending, windows = None, None
                if c.num_shards > 1:
                    fn = self._executor(cap, index.layout_key,
                                        sharded_index=index)
                    pending = fn.start(index.arrays, arr, lens, timed=False)
                    res = pending.res
                else:  # a non-blocking call: the results stay on device
                    fn = self._executor(cap)
                    res = fn(index, arr, lens)
                    if tr.enabled or self._roofline_on():
                        if self._watcher is None:
                            self._watcher = _StageWatcher()
                        windows = self._watcher.watch(fn.last_stages)
            self._pending = _PendingFlush(cap, reqs, fn, res, pending, epoch,
                                          lens, t_flush, windows)
        except BaseException:
            self._pending = prev  # the worker handler fails prev too
            raise
        if prev is None:
            return 0, disp.t_end or time.monotonic()
        self.metrics.counter("flushes_overlapped").inc()
        return self._finish_flush(prev, disp.t_end)

    def _finish_pending(self, t_start: float | None = None
                        ) -> tuple[int, float]:
        prev, self._pending = self._pending, None
        if prev is None:
            return 0, time.monotonic()
        return self._finish_flush(prev, t_start)

    def _finish_flush(self, state: _PendingFlush, t_start: float | None
                      ) -> tuple[int, float]:
        """Wait for a dispatched flush, then fetch and deliver its
        results; returns ``(reads delivered, end of the flush)``.  The
        flush and its ``device_wait`` start at ``t_start``, where the
        worker's last span ended."""
        c, tr = self.config, self.tracer
        cap, reqs = state.cap, state.reqs
        try:
            with tr.span("flush", t_start=t_start, bucket_cap=cap,
                         batch=len(reqs), workload=c.workload,
                         shards=c.num_shards, pipelined=True) as flush:
                with tr.span("device_wait", t_start=t_start,
                             bucket_cap=cap) as wait:
                    jax.block_until_ready(state.res)
                    if state.windows is not None:
                        state.windows[1].wait()
                tid, stats = None, None
                if state.pending is None:  # one chip
                    res = self._fetch(cap, state.res, t_start=wait.t_end)
                    times = state.windows[0] if state.windows else ()
                    # device windows, which overlap the worker's spans
                    tid = f"{self._worker.name} device"
                else:
                    with tr.span("fetch", t_start=wait.t_end,
                                 bucket_cap=cap):
                        res, times = state.fn.finish(state.pending)
                    state.fn.last_times = list(times)
                    stats = state.pending.stats
                kc = self._record_roofline(cap, times)
                self._deliver(cap, reqs, state.epoch, state.lens, res, stats)
                t_replay = self._replay(flush, cap, reqs, state.t_flush,
                                        times, kc, tid=tid)
        except BaseException as e:
            # this flush's futures die here; the worker handler that
            # re-raises cannot see them anymore (self._pending is clear)
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)
            raise
        return len(reqs), self._end_flush(flush, cap, t_replay)

    def _roofline_on(self) -> bool:
        return self.roofline is not None and self.roofline.enabled

    def _record_roofline(self, cap: int, times):
        """Per-kernel analytic counters of a linear flush's align stage:
        the op/byte model is exact, sharded or not — the mesh split
        changes the launch layout, not the per-read totals (graph
        executors: not modeled yet).  None when nothing records."""
        c, rf = self.config, self.roofline
        if not self._roofline_on() or c.workload != "linear":
            return None
        from repro import align as align_dispatch

        align_s = next((t1 - t0 for name, t0, t1, _ in times
                        if name in ("align", "align_shard")), None)
        return rf.record_flush(
            self.align_backend, cap, c.genasm.k, c.max_batch,
            align_s=align_s,
            block_bt=align_dispatch.block_size_for(
                self.align_backend, cap, c.genasm.k, c.max_batch))

    def _lengths(self, cap: int, reqs: list[_Request]) -> np.ndarray:
        """``[max_batch]`` read lengths as `encode.batch_reads` gives them:
        trimmed to the cap, 0 on padding rows."""
        lens = np.zeros(self.config.max_batch, np.int32)
        lens[:len(reqs)] = [min(r.length, cap) for r in reqs]
        return lens

    def _encode(self, cap: int, reqs: list[_Request],
                t_start: float | None = None):
        """The flush's reads as one ``[max_batch, cap]`` int8 batch, and
        the ``encode`` span (its ``t_end`` is where the next one starts)."""
        with self.tracer.span("encode", t_start=t_start,
                              bucket_cap=cap) as span:
            arr = encode.batch_reads(
                [r.read for r in reqs]
                + [np.zeros(0, np.int8)] * (self.config.max_batch - len(reqs)),
                cap)[0]
        return arr, span

    def _fetch(self, cap: int, res, t_start: float | None = None
               ) -> _HostResult:
        """Device→host copies of the result fields `_deliver` reads."""
        with self.tracer.span("fetch", t_start=t_start, bucket_cap=cap):
            host = _HostResult(
                np.asarray(res.position), np.asarray(res.distance),
                np.asarray(res.ops), np.asarray(res.n_ops),
                np.asarray(res.path) if self.config.workload == "graph"
                else None)
            # the last reference to the device results (callers pass them
            # unbound): their buffers are freed here, not unnamed later
            del res
        return host

    def _deliver(self, cap: int, reqs: list[_Request], epoch, lens, res,
                 stats) -> None:
        """Flush tail shared by both modes, on host results: metrics,
        cache, futures."""
        c, m = self.config, self.metrics
        with self.tracer.span("emit", bucket_cap=cap):
            pos, dist, ops, n_ops = (res.position, res.distance, res.ops,
                                     res.n_ops)
            paths = res.path if c.workload == "graph" else None
            m.counter("batches_flushed").inc()
            m.counter(f"batches_flushed_cap{cap}").inc()
            m.histogram("batch_occupancy", lo=1e-3, hi=1.0).observe(
                len(reqs) / c.max_batch)
            real = int(sum(min(r.length, cap) for r in reqs))
            m.counter("bases_useful").inc(real)
            m.counter("bases_padded_read").inc(len(reqs) * cap - real)
            m.counter("bases_padded_slot").inc(
                (c.max_batch - len(reqs)) * cap)
            if stats:  # graph executors: tile-screen / DC-occupancy
                for name, v in stats.items():
                    m.counter(f"graph_{name}").inc(int(v))

            done = self._clock()
            results = []
            for i, r in enumerate(reqs):
                out = ServeResult(
                    position=int(pos[i]), distance=int(dist[i]),
                    ops=ops[i].copy(), n_ops=int(n_ops[i]),
                    read_len=int(lens[i]), bucket_cap=cap,
                    cached=False, latency_s=done - r.t_submit,
                    path=None if paths is None else paths[i].copy())
                self.cache.put(r.read, epoch, out, digest=r.digest)
                m.histogram("latency_s").observe(out.latency_s)
                results.append(out)
            # resolve futures before releasing drain(): a drained
            # engine has every result observable, not merely computed
            for r, out in zip(reqs, results):
                r.future.set_result(out)

    def _replay(self, flush, cap: int, reqs: list[_Request], t_flush: float,
                times, kc, tid: str | None = None) -> float | None:
        """The tracer's per-flush bookkeeping, the flush's last work: each
        request's queue wait (async: waits overlap the previous flush's
        compute) and the executor's per-stage monotonic windows
        (seed_filter/prefilter/dc_filter/scatter/merge_device/align/
        align_shard, with compile/dc_rows/shard attrs; the align span
        carries the analytic counters when modeled), all as children of
        ``flush``; ``tid`` puts the stage windows on a track of their own
        (device windows, which overlap the worker's host spans).
        Returns when it started (None with tracing off): the
        caller records ``trace_replay`` from there to the flush's end, so
        that one span holds what tracing costs the worker, closing the
        flush span included."""
        tr = self.tracer
        if not tr.enabled:
            return None
        t0 = time.monotonic()
        parent = flush.span_id
        for r in reqs:
            tr.add("enqueue_wait", r.t_submit, t_flush, parent=parent,
                   bucket_cap=cap, async_=True)
        for name, t_a, t_b, attrs in times:
            if name in ("align", "align_shard") and kc is not None:
                attrs = {**attrs, "word_ops": kc.word_ops,
                         "hbm_bytes": kc.hbm_bytes}
            tr.add(name, t_a, t_b, parent=parent, tid=tid, bucket_cap=cap,
                   **attrs)
        return t0

    def _end_flush(self, flush, cap: int, t_replay: float | None) -> float:
        """After the flush span closes: its ``trace_replay`` child, and the
        flush's end (where the next ``worker_wait`` starts)."""
        if t_replay is None:
            return time.monotonic()
        self.tracer.add("trace_replay", t_replay, flush.t_end,
                        parent=flush.span_id, bucket_cap=cap)
        return flush.t_end

    def _execute(self, cap: int, reqs: list[_Request], t_pick: float
                 ) -> tuple[int, float]:
        """One flush; returns ``(reads delivered, end of the flush)``.

        The flush and its ``dispatch`` start at ``t_pick``, where the
        worker's ``worker_wait`` ended, and the next ``worker_wait``
        starts at the flush's end: no moment of the worker falls between
        them, not even this call and its return (freeing the locals)."""
        c = self.config
        tr = self.tracer
        t_flush = self._clock()
        with tr.span("flush", t_start=t_pick, bucket_cap=cap,
                     batch=len(reqs), workload=c.workload,
                     shards=c.num_shards) as flush:
            with tr.span("dispatch", t_start=t_pick, bucket_cap=cap):
                index, epoch = self.index.current()
                payload = index.arrays
                if c.num_shards > 1:
                    fn = self._executor(cap, index.layout_key,
                                        sharded_index=index)
                else:  # graph: syncs mid-flush to pick a tile rung
                    fn = self._executor(cap, index.geometry)
                lens = self._lengths(cap, reqs)
                # copied here, so the executor's own jnp.asarray of it is
                # a no-op and no host work falls between encode and the
                # first device stage
                dev_lens = jnp.asarray(lens)
            arr, _ = self._encode(cap, reqs)
            # unbound, so that fetch frees the device results
            host = self._fetch(cap, fn(payload, arr, dev_lens))
            self._deliver(cap, reqs, epoch, lens, host,
                          getattr(fn, "last_stats", None))
            times = getattr(fn, "last_times", ())
            kc = self._record_roofline(cap, times)
            t_replay = self._replay(flush, cap, reqs, t_flush, times, kc)
        return len(reqs), self._end_flush(flush, cap, t_replay)
