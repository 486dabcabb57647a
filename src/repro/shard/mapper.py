"""Shard-parallel linear mapping: scatter reads, merge candidates, align.

Execution plan per flush (the dissertation's channel dataflow, DESIGN.md
§11):

1. **Scatter** — the read batch is broadcast to every shard; each shard
   seeds against its own minimizer-table slice and GenASM-DC-filters its
   own ``shard_candidates`` best diagonals, entirely inside its haloed
   reference slice.  This stage runs under ``shard_map`` over a
   ``("shard",)`` mesh (specs from `repro.dist.sharding.stacked_specs`)
   when enough devices exist, else under a ``vmap`` over the stacked
   shard axis — the two lower to the same math, so results are
   bit-identical.
2. **Merge** — per-shard winners carry *global* (filter distance,
   refined position) pairs plus their ``[t_cap]`` alignment window
   bytes; a device argmin-reduce on the packed monotone uint64
   ``(distance, position)`` key (`repro.shard.merge`) picks the
   lexicographic minimum per read *without leaving the device* — the
   host lex merge survives only as the reference implementation
   (``merge_host``) for the differential suite and chaos drills.
   Windows in overlap halos are byte-identical across neighbouring
   shards, so duplicated boundary candidates dedup by construction.
3. **Align** — one batched `repro.align.align_batch` call on the
   winning windows (any registered backend); no stage after the merge
   touches the sharded reference.  With ``align_sharded=True`` the
   batch is round-robin split into ``[S, B/S]`` blocks and aligned
   under the same shard mesh (``dist.sharding.stacked_specs`` layout);
   per-read results are independent, so the split is bit-neutral.

The executor also exposes a two-phase ``start()``/``finish()`` surface:
``start`` dispatches scatter → device merge → align and returns a
:class:`PendingBatch` of device-resident results without blocking on
the align program, ``finish`` materializes it.  The serve engine's
``pipelined`` mode uses this to overlap batch *i*'s align against
batch *i+1*'s scatter (double buffering); ``__call__`` is simply
``finish(start(...))`` with per-stage timing in between.

The per-shard stage calls `repro.core.mapper.seed_filter_read` — the
*same* function the single-device mapper runs with offset 0 — which is
what makes ``num_shards=1`` vs ``N`` PAF output byte-identical.

Identity caveat: per-shard seeding keeps each shard's top
``shard_candidates`` diagonals *by local vote count*, so the merged
candidate set is guaranteed to contain the single-device winner only
while that winner ranks within ``shard_candidates`` in its owning
shard's table.  Real reads satisfy this easily (the true diagonal
dominates local voting, even split across a cut — pinned by the golden
and boundary suites); a highly repetitive reference combined with a
reduced per-shard budget (``shard_candidates < max_candidates``, the
throughput configuration) can in principle evict it.  Serve with the
full per-shard budget when byte-stability across re-sharding is a hard
requirement.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mapper as core_mapper
from repro.core.genasm import GenASMConfig
from repro.core.mapper import MapResult, POS_SENTINEL
from repro.dist import sharding as dist_sharding

from . import merge as shard_merge
from .partition import ShardArrays, ShardedIndex


class ShardStageResult(NamedTuple):
    """Per-(shard, read) winner of the scatter stage, global coordinates."""

    distance: jnp.ndarray  # [S, B] int32 filter distance (filter_k+1 = none)
    position: jnp.ndarray  # [S, B] int32 refined global start (sentinel=none)
    text: jnp.ndarray  # [S, B, t_cap] int8 alignment window at position
    t_len: jnp.ndarray  # [S, B] int32 valid window length


class PendingBatch(NamedTuple):
    """In-flight batch from ``start()``: device results + closed spans.

    ``res`` holds the executor's result tree with *device* leaves (the
    align program may still be running); ``times`` the already-closed
    ``(stage, t0, t1, attrs)`` windows; ``tail`` the name/attrs of the
    span ``finish()`` will close from ``t_dispatch`` to materialization
    (None when the result is already host-resident, e.g. the graph
    zero-survivor short-circuit); ``stats`` the graph executors'
    ``last_stats`` payload (None for linear).
    """

    res: object
    times: tuple
    t_dispatch: float
    tail: tuple | None  # (stage_name, attrs)
    stats: dict | None = None


def required_halo(*, p_cap: int, filter_bits: int, filter_k: int,
                  t_cap: int) -> int:
    """Smallest overlap halo that loses no boundary mapping.

    Left of a core: a candidate diagonal seeded by an entry at the core
    boundary can start up to ``p_cap`` bases earlier (read-relative
    seed offset) plus 32 bases of diagonal-bucket rounding, and the
    filter reads ``margin = filter_k + 32`` bases of drift before it.
    Right of a core: the filter region extends ``filter_bits + margin``
    past the candidate and the refined anchor needs ``t_cap`` bases of
    alignment text after it.
    """
    margin = filter_k + 32
    left = p_cap + 32 + margin
    right = filter_bits + 2 * margin + t_cap
    return max(left, right)


def validate_geometry(sharded: ShardedIndex, *, p_cap: int, filter_bits: int,
                      filter_k: int, t_cap: int) -> None:
    """Raise if the layout's halo cannot cover this mapping geometry."""
    need = required_halo(p_cap=p_cap, filter_bits=filter_bits,
                         filter_k=filter_k, t_cap=t_cap)
    if sharded.layout.halo < need:
        raise ValueError(
            f"shard halo {sharded.layout.halo} < {need} required for "
            f"p_cap={p_cap}, filter_bits={filter_bits}, "
            f"filter_k={filter_k}, t_cap={t_cap}; rebuild the sharded "
            f"index with halo >= {need}")


def _stage_one_shard(ref_row, off_row, hash_row, pos_row, reads, read_lens,
                     *, ref_len, p_cap, t_cap, filter_bits, filter_k,
                     shard_candidates, minimizer_w, minimizer_k):
    """Seed + filter the whole read batch against one shard's slice."""
    f = partial(
        core_mapper.seed_filter_read, ref_row, off_row, ref_len,
        hash_row, pos_row, p_cap=p_cap, t_cap=t_cap,
        filter_bits=filter_bits, filter_k=filter_k,
        max_candidates=shard_candidates, minimizer_w=minimizer_w,
        minimizer_k=minimizer_k)
    sf = jax.vmap(f)(reads, read_lens)
    return sf.distance, sf.position, sf.text, sf.t_len


class ShardedMapExecutor:
    """Compiled scatter/merge/align pipeline for one sharded geometry.

    Holds three jitted programs — the shard stage (``shard_map`` over a
    shard mesh when ``jax.device_count() >= num_shards``, else a
    stacked ``vmap``), the packed-key device merge
    (`repro.shard.merge.merge_linear` under an x64 scope), and the
    align stage (optionally sharded over the same mesh).  Construct
    once per (index geometry, mapping parameters) and call with
    ``(ShardArrays, reads, lens)``; the serve engine caches executors
    exactly like its single-device ones.
    """

    def __init__(self, sharded: ShardedIndex, *,
                 cfg: GenASMConfig = GenASMConfig(),
                 p_cap: int = 256,
                 filter_bits: int = 128,
                 filter_k: int = 12,
                 shard_candidates: int = 4,
                 minimizer_w: int | None = None,
                 minimizer_k: int | None = None,
                 backend: str | None = None,
                 block_bt: int | None = None,
                 force_vmap: bool = False,
                 align_sharded: bool = False,
                 trace_hook=None):
        t_cap = p_cap + 2 * cfg.w
        filter_bits = min(filter_bits, p_cap)
        validate_geometry(sharded, p_cap=p_cap, filter_bits=filter_bits,
                          filter_k=filter_k, t_cap=t_cap)
        self.num_shards = sharded.num_shards
        self.filter_k = filter_k
        self.backend = backend
        self.align_sharded = align_sharded
        user_hook = trace_hook
        self._compiled: set = set()  # stage keys that have traced

        def hook(key):
            self._compiled.add(key)
            if user_hook is None:
                return
            try:
                user_hook(key)
            except TypeError:  # legacy no-arg hooks
                user_hook()

        stage = partial(
            _stage_one_shard,
            ref_len=sharded.ref_len, p_cap=p_cap, t_cap=t_cap,
            filter_bits=filter_bits, filter_k=filter_k,
            shard_candidates=shard_candidates,
            minimizer_w=sharded.minimizer_w if minimizer_w is None
            else minimizer_w,
            minimizer_k=sharded.minimizer_k if minimizer_k is None
            else minimizer_k)

        mesh = None if force_vmap else dist_sharding.shard_mesh(
            self.num_shards)
        self.spmd = mesh is not None
        if self.spmd:
            from jax.sharding import PartitionSpec as P

            # check_vma=False: the per-shard bodies scan from replicated
            # initial carries; every output is per-shard by construction
            arr_specs = tuple(dist_sharding.stacked_specs(
                sharded.arrays, mesh))

            def block_stage(refs, offs, hashes, poss, reads, lens):
                hook(("scatter",))
                out = stage(refs[0], offs[0], hashes[0], poss[0], reads, lens)
                return jax.tree.map(lambda x: x[None], out)

            self._stage = jax.jit(jax.shard_map(
                block_stage, mesh=mesh,
                in_specs=arr_specs + (P(), P()),
                out_specs=P("shard"), check_vma=False))
        else:
            def stacked_stage(refs, offs, hashes, poss, reads, lens):
                hook(("scatter",))
                return jax.vmap(
                    lambda r, o, h, p: stage(r, o, h, p, reads, lens)
                )(refs, offs, hashes, poss)

            self._stage = jax.jit(stacked_stage)

        def align_core(text, reads, lens, t_len, pos, fd):
            from repro import align as align_dispatch

            lens = lens.astype(jnp.int32)
            pat = jnp.where(jnp.arange(p_cap)[None, :] < lens[:, None],
                            reads[:, :p_cap], core_mapper.WILDCARD
                            ).astype(jnp.int8)
            res = align_dispatch.align_batch(
                text, pat, lens, t_len, cfg=cfg, backend=backend,
                p_cap=p_cap, block_bt=block_bt)
            failed = res.failed | (fd > filter_k)
            return MapResult(
                position=jnp.where(failed, -1, pos).astype(jnp.int32),
                distance=jnp.where(failed, -1, res.distance),
                ops=res.ops, n_ops=res.n_ops, failed=failed)

        def align_stage(text, reads, lens, t_len, pos, fd):
            hook(("align",))
            return align_core(text, reads, lens, t_len, pos, fd)

        s = self.num_shards

        def align_stage_sharded(text, reads, lens, t_len, pos, fd):
            # round-robin split of the merged winners into [S, B/S]
            # blocks on the shard mesh; per-read results are
            # independent, so the split (and its padding) is bit-neutral
            hook(("align_shard",))
            b = text.shape[0]
            bs = -(-b // s)  # rows per shard, last block zero-padded

            def blocked(x):
                x = jnp.pad(x, ((0, bs * s - b),)
                            + ((0, 0),) * (x.ndim - 1))
                return x.reshape((s, bs) + x.shape[1:])

            args = tuple(blocked(x)
                         for x in (text, reads, lens, t_len, pos, fd))
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                def block(*rows):
                    out = align_core(*[r[0] for r in rows])
                    return jax.tree.map(lambda y: y[None], out)

                out = jax.shard_map(
                    block, mesh=mesh, in_specs=(P("shard"),) * 6,
                    out_specs=P("shard"), check_vma=False)(*args)
            else:
                out = jax.vmap(align_core)(*args)
            return jax.tree.map(
                lambda y: y.reshape((bs * s,) + y.shape[2:])[:b], out)

        self._align = jax.jit(
            align_stage_sharded if align_sharded else align_stage)
        self._align_stage_name = ("align_shard" if align_sharded
                                  else "align")
        # packed-key argmin-reduce: winners picked on device, only the
        # [B]-sized merged rows ever needed by the align launch
        self._merge = jax.jit(shard_merge.merge_linear)
        # the argmin collapses the shard axis but leaves its outputs
        # replicated across the mesh; a full-batch align traced on
        # replicated operands re-runs on every device, so the tiny
        # merged rows are committed to one device first.  A mesh-split
        # align partitions the work itself and must see mesh-addressable
        # inputs, so it keeps them replicated.
        self._off_mesh = (None if mesh is None or align_sharded
                          else mesh.devices.flat[0])
        # (stage, t0, t1, attrs) monotonic windows from the last call —
        # the serve engine replays them as child spans of its flush span
        self.last_times: list[tuple[str, float, float, dict]] = []

    def stage(self, arrays: ShardArrays, reads, read_lens
              ) -> ShardStageResult:
        """Run the scatter stage: per-shard winners for the whole batch."""
        fd, pos, text, t_len = self._stage(
            arrays.refs, arrays.offsets, arrays.hashes, arrays.positions,
            jnp.asarray(reads), jnp.asarray(read_lens, jnp.int32))
        return ShardStageResult(distance=fd, position=pos, text=text,
                                t_len=t_len)

    @staticmethod
    def merge_host(stage: ShardStageResult):
        """Reference host merge: lex-min ``(distance, position)`` per read.

        The pre-device-merge implementation, kept as the independently
        coded oracle for the differential suite
        (``tests/test_shard_merge.py``) — the packed-key argmin must
        match it bit for bit, including the low-shard tie-break.
        Overlap-halo duplicates carry identical (distance, position,
        window bytes) in both neighbouring shards, so whichever copy
        argmin lands on yields the same alignment — dedup for free.
        Returns ``(fd, pos, text, t_len, winner_shard)`` numpy arrays.
        """
        fd = np.asarray(stage.distance)
        pos = np.asarray(stage.position)
        m = fd.min(axis=0)
        pm = np.where(fd == m[None, :], pos, POS_SENTINEL)
        win = pm.argmin(axis=0)
        cols = np.arange(fd.shape[1])
        return (m, pm[win, cols], np.asarray(stage.text)[win, cols],
                np.asarray(stage.t_len)[win, cols], win)

    # chaos drills (failover.py) and older callers used ``ex.merge``
    merge = merge_host

    def merge_device(self, stage: ShardStageResult):
        """Packed-key argmin-reduce on device; winners stay device-resident.

        Returns ``(fd, pos, text, t_len, winner_shard)`` as jax arrays —
        same contract and tie-break as `merge_host`, no host round trip.
        """
        with shard_merge.x64_scope():
            out = self._merge(stage.distance, stage.position,
                              stage.text, stage.t_len)
        if self._off_mesh is not None:
            out = jax.device_put(out, self._off_mesh)
        return out

    def start(self, arrays: ShardArrays, reads, read_lens, *,
              timed: bool = True) -> PendingBatch:
        """Dispatch scatter → device merge → align without materializing.

        The returned :class:`PendingBatch` holds device-resident
        results; `finish` blocks and converts.  ``timed=False`` skips
        the inter-stage ``block_until_ready`` syncs (and their spans) —
        the lowest-overhead dispatch for pipelined serving, where
        per-stage attribution is sacrificed for overlap.
        """
        c_sc = ("scatter",) not in self._compiled
        align_key = (self._align_stage_name,)
        c_al = align_key not in self._compiled
        times: list[tuple[str, float, float, dict]] = []
        t0 = time.monotonic()
        st = self.stage(arrays, reads, read_lens)
        if timed:
            jax.block_until_ready(st)
            t1 = time.monotonic()
            times.append(("scatter", t0, t1,
                          {"compile": c_sc, "shards": self.num_shards}))
        fd, pos, text, t_len, _win = self.merge_device(st)
        if timed:
            jax.block_until_ready(fd)
            t2 = time.monotonic()
            times.append(("merge_device", t1, t2,
                          {"shards": self.num_shards}))
        else:
            t2 = time.monotonic()
        res = self._align(text, jnp.asarray(reads),
                          jnp.asarray(read_lens, jnp.int32),
                          t_len, pos, fd)
        return PendingBatch(res=res, times=tuple(times), t_dispatch=t2,
                            tail=(self._align_stage_name,
                                  {"compile": c_al,
                                   "sharded": self.align_sharded}))

    @staticmethod
    def finish(pending: PendingBatch):
        """Materialize a `start` batch → ``(numpy result, stage times)``."""
        res = jax.tree_util.tree_map(np.asarray, pending.res)
        times = pending.times
        if pending.tail is not None:
            name, attrs = pending.tail
            times = times + ((name, pending.t_dispatch, time.monotonic(),
                              attrs),)
        return res, times

    def __call__(self, arrays: ShardArrays, reads, read_lens) -> MapResult:
        """Map one batch: scatter → device merge → batched align."""
        res, times = self.finish(self.start(arrays, reads, read_lens))
        self.last_times = list(times)
        return res


# bounded LRU: a long-running process whose refresh() cycles through
# reference lengths must not accumulate compiled executors forever
_EXECUTORS: OrderedDict[tuple, ShardedMapExecutor] = OrderedDict()
_EXECUTOR_CACHE_CAP = 8


def get_executor(
    sharded: ShardedIndex,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int = 256,
    filter_bits: int = 128,
    filter_k: int = 12,
    shard_candidates: int = 4,
    backend: str | None = None,
    block_bt: int | None = None,
    force_vmap: bool = False,
    align_sharded: bool = False,
) -> ShardedMapExecutor:
    """Cached :class:`ShardedMapExecutor` for one (geometry, params) key.

    Shared by `map_batch_sharded` and `failover.map_batch_with_failover`
    so repeated batches (including degraded-mode retries) never
    recompile; the LRU bound evicts executors of abandoned layouts.
    """
    key = (sharded.layout_key, sharded.minimizer_w, sharded.minimizer_k,
           cfg, p_cap, filter_bits, filter_k, shard_candidates,
           backend, block_bt, force_vmap, align_sharded)
    ex = _EXECUTORS.get(key)
    if ex is None:
        ex = ShardedMapExecutor(
            sharded, cfg=cfg, p_cap=p_cap, filter_bits=filter_bits,
            filter_k=filter_k, shard_candidates=shard_candidates,
            backend=backend, block_bt=block_bt, force_vmap=force_vmap,
            align_sharded=align_sharded)
        _EXECUTORS[key] = ex
        while len(_EXECUTORS) > _EXECUTOR_CACHE_CAP:
            _EXECUTORS.popitem(last=False)
    else:
        _EXECUTORS.move_to_end(key)
    return ex


def map_batch_sharded(
    sharded: ShardedIndex,
    reads,
    read_lens,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int = 256,
    filter_bits: int = 128,
    filter_k: int = 12,
    shard_candidates: int = 4,
    backend: str | None = None,
    block_bt: int | None = None,
    force_vmap: bool = False,
    align_sharded: bool = False,
    pipelined: bool = False,
) -> MapResult:
    """Map a read batch against a sharded reference index.

    ``reads`` is ``[B, >=p_cap] int8`` with ``read_lens [B]`` valid
    lengths; returns the same :class:`repro.core.mapper.MapResult`
    (numpy leaves) as the single-device `core.mapper.map_batch` —
    byte-identical positions, distances, and CIGARs for any shard
    count, with the align stage sharded or not and through the
    pipelined (``start``/``finish``) dispatch path or the timed one.
    Executors are cached per (geometry, parameters).
    """
    ex = get_executor(
        sharded, cfg=cfg, p_cap=p_cap, filter_bits=filter_bits,
        filter_k=filter_k, shard_candidates=shard_candidates,
        backend=backend, block_bt=block_bt, force_vmap=force_vmap,
        align_sharded=align_sharded)
    if pipelined:
        res, times = ex.finish(ex.start(sharded.arrays, reads, read_lens,
                                        timed=False))
        ex.last_times = list(times)
        return res
    return ex(sharded.arrays, reads, read_lens)
