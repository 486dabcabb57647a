"""Shard-parallel graph mapping: the GAF twin of `shard.mapper`.

Same pipeline as the whole-graph `repro.graph.mapper.GraphMapExecutor`,
scattered: every shard runs the seed + q-gram tile screen
(`tile_prefilter`) over its own :class:`~repro.graph.mapper.GraphView`,
a host sync on the per-shard survivor counts picks one shared
`tile_rung`, each shard compacts its survivors into that many DC rows
(`graph_candidate_stage` with ``pf``/``n_cap``), per-shard winners merge
**on device** by an argmin-reduce over the packed monotone uint64
``(filter distance, origin node, tile)`` key (`repro.shard.merge`,
global coordinates; the host lex merge survives as ``merge_host``, the
differential oracle), and one batched graph ``align_batch`` call
finishes — optionally sharded over the same mesh
(``align_sharded=True``) and dispatchable without blocking through the
``start()``/``finish()`` pipeline surface.  The screen and compaction are bitwise-neutral per shard
(see `graph/mapper`), and the merge rule is the same one the whole-graph
mapper applies across its candidate axis — so GAF output stays
byte-identical at 1 and N shards, prefilter on or off.  Winners travel
with their packed window bytes *and* per-node backbone coordinates
(``bwin``), so the align stage needs no graph arrays at all.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.genasm import GenASMConfig
from repro.core.mapper import POS_SENTINEL
from repro.dist import sharding as dist_sharding
from repro.graph.mapper import (CandidateStageResult, GraphMapResult,
                                GraphView, _env_prefilter, align_winners,
                                graph_backend_name, graph_candidate_stage,
                                tile_prefilter, tile_rung, unmapped_result)

from . import merge as shard_merge
from .graph_partition import GraphShardArrays, ShardedGraphIndex
from .mapper import PendingBatch


def validate_graph_geometry(sharded: ShardedGraphIndex, *, p_cap: int,
                            filter_k: int, cfg: GenASMConfig) -> None:
    """Raise if the window/halo geometry cannot cover this mapping setup."""
    from repro.core.segram.graph import HOP_LIMIT

    t_cap = p_cap + 2 * cfg.w
    if sharded.window < t_cap:
        raise ValueError(
            f"index window {sharded.window} < the {t_cap}-node alignment "
            f"text at p_cap {p_cap}: the tile screen would not cover the "
            f"gathered windows; rebuild the index with window >= {t_cap}")
    need = p_cap + 32 + HOP_LIMIT + filter_k
    if sharded.layout.halo < need:
        raise ValueError(
            f"graph shard halo {sharded.layout.halo} < {need} required "
            f"for p_cap={p_cap}, filter_k={filter_k}; rebuild with "
            f"halo >= {need}")


def _shard_view(gtext, tbase, nob, nboff, bb, nbase, hashes, poss, tbloom,
                tslack) -> GraphView:
    """One shard's arrays (`GraphShardArrays` row order) as a GraphView."""
    return GraphView(
        gtext=gtext, tile_base=tbase,
        node_of_backbone=nob, nb_offset=nboff, backbone=bb,
        node_base=nbase, idx_hashes=hashes, idx_positions=poss,
        tile_bloom=tbloom, tile_slack=tslack)


def _pf_one_shard(*args, static):
    """One graph shard's seed + tile screen over the whole read batch."""
    arrs, (reads, lens) = args[:-2], args[-2:]
    return tile_prefilter(_shard_view(*arrs), reads, lens, **static)


def _stage_one_shard(*args, n_cap, static):
    """One shard's compacted candidate stage (survivors from ``pf``)."""
    arrs, (reads, lens, pf) = args[:-3], args[-3:]
    return graph_candidate_stage(_shard_view(*arrs), reads, lens, pf=pf,
                                 n_cap=n_cap, **static)


class ShardedGraphMapExecutor:
    """Compiled scatter/screen/merge/align pipeline for one sharded graph.

    Mirrors `graph.mapper.GraphMapExecutor` across shards: a
    ``shard_map`` (or stacked ``vmap``) prefilter stage, a host sync
    that picks one `tile_rung` from the worst shard's survivor count, a
    per-rung compiled compacted candidate stage, the host lexicographic
    merge, and one jitted graph-align stage.  ``trace_hook`` (if given)
    is called with a hashable stage key at trace time —
    ``("prefilter",)``, ``(n_cap,)`` per rung, and ``("align",)`` — or
    with no argument if it doesn't accept one (legacy align-only hook).
    ``last_stats`` carries pruning/occupancy counters for the engine.
    """

    def __init__(self, sharded: ShardedGraphIndex, *,
                 cfg: GenASMConfig = GenASMConfig(),
                 p_cap: int = 256,
                 filter_bits: int = 128,
                 filter_k: int = 12,
                 shard_candidates: int = 4,
                 backend: str | None = None,
                 block_bt: int | None = None,
                 force_vmap: bool = False,
                 align_sharded: bool = False,
                 prefilter: bool | None = None,
                 trace_hook=None):
        from repro import align as align_dispatch

        validate_graph_geometry(sharded, p_cap=p_cap, filter_k=filter_k,
                                cfg=cfg)
        shard_merge.check_graph_domain(n_tiles=sharded.n_tiles,
                                       filter_k=filter_k)
        self.align_sharded = align_sharded
        self.num_shards = sharded.num_shards
        self.backend = graph_backend_name(backend)
        self.cfg = cfg
        self.p_cap = p_cap
        self.shard_candidates = shard_candidates
        self.prefilter = _env_prefilter(prefilter)
        t_cap = p_cap + 2 * cfg.w

        self._compiled: set = set()  # stage keys that have traced

        def hook(key):
            self._compiled.add(key)
            if trace_hook is None:
                return
            try:
                trace_hook(key)
            except TypeError:
                trace_hook()

        self._hook = hook
        static_pf = dict(
            tile_stride=sharded.tile_stride, n_tiles=sharded.n_tiles,
            backbone_len=sharded.ref_len,
            filter_bits=min(filter_bits, p_cap), filter_k=filter_k,
            max_candidates=shard_candidates,
            minimizer_w=sharded.minimizer_w,
            minimizer_k=sharded.minimizer_k, prefilter=self.prefilter)
        static = dict(
            tile_stride=sharded.tile_stride, n_tiles=sharded.n_tiles,
            backbone_len=sharded.ref_len, n_nodes=sharded.n_nodes,
            margin=sharded.margin, t_cap=t_cap,
            filter_bits=min(filter_bits, p_cap),
            filter_k=filter_k, max_candidates=shard_candidates,
            minimizer_w=sharded.minimizer_w,
            minimizer_k=sharded.minimizer_k,
            use_kernel=align_dispatch.get_backend(self.backend).uses_pallas,
            block_bt=block_bt, interpret=align_dispatch.needs_interpret())
        pf_fn = partial(_pf_one_shard, static=static_pf)

        mesh = None if force_vmap else dist_sharding.shard_mesh(
            self.num_shards)
        self.spmd = mesh is not None
        if self.spmd:
            from jax.sharding import PartitionSpec as P

            # check_vma=False: the per-shard bodies scan from replicated
            # initial carries; every output is per-shard by construction
            arr_specs = tuple(dist_sharding.stacked_specs(
                sharded.arrays, mesh))

            def block_pf(*args):
                self._hook(("prefilter",))
                arrs, (reads, lens) = args[:-2], args[-2:]
                out = pf_fn(*[a[0] for a in arrs], reads, lens)
                return jax.tree.map(lambda x: x[None], out)

            self._pf = jax.jit(jax.shard_map(
                block_pf, mesh=mesh, in_specs=arr_specs + (P(), P()),
                out_specs=P("shard"), check_vma=False))

            def make_stage(n_cap):
                stage = partial(_stage_one_shard, n_cap=n_cap,
                                static=static)

                def block_stage(*args):
                    self._hook((n_cap,))
                    arrs, (reads, lens, pf) = args[:-3], args[-3:]
                    pf0 = jax.tree.map(lambda x: x[0], pf)
                    out = stage(*[a[0] for a in arrs], reads, lens, pf0)
                    return jax.tree.map(lambda x: x[None], out)

                return jax.jit(jax.shard_map(
                    block_stage, mesh=mesh,
                    in_specs=arr_specs + (P(), P(), P("shard")),
                    out_specs=P("shard"), check_vma=False))
        else:
            def stacked_pf(*args):
                self._hook(("prefilter",))
                arrs, (reads, lens) = args[:-2], args[-2:]
                return jax.vmap(
                    lambda *rows: pf_fn(*rows, reads, lens))(*arrs)

            self._pf = jax.jit(stacked_pf)

            def make_stage(n_cap):
                stage = partial(_stage_one_shard, n_cap=n_cap,
                                static=static)

                def stacked_stage(*args):
                    self._hook((n_cap,))
                    arrs, (reads, lens, pf) = args[:-3], args[-3:]
                    return jax.vmap(
                        lambda *rows: stage(*rows[:-1], reads, lens,
                                            rows[-1]))(*arrs, pf)

                return jax.jit(stacked_stage)

        self._make_stage = make_stage
        self._stages: dict[int, object] = {}

        def align_core(merged: CandidateStageResult, reads, lens):
            return align_winners(merged, reads, lens, cfg=cfg, p_cap=p_cap,
                                 backend=self.backend, block_bt=block_bt)

        def align_stage(merged: CandidateStageResult, reads, lens):
            self._hook(("align",))
            return align_core(merged, reads, lens)

        s = self.num_shards

        def align_stage_sharded(merged: CandidateStageResult, reads, lens):
            # round-robin [S, B/S] split of the merged winners on the
            # shard mesh; windows/bwin travel with the winner, so each
            # block aligns without graph arrays — bit-neutral per read
            self._hook(("align_shard",))
            b = reads.shape[0]
            bs = -(-b // s)

            def blocked(x):
                x = jnp.pad(x, ((0, bs * s - b),)
                            + ((0, 0),) * (x.ndim - 1))
                return x.reshape((s, bs) + x.shape[1:])

            margs = jax.tree.map(blocked, merged)
            rargs = (blocked(reads), blocked(lens))
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                def block(m, r, ln):
                    out = align_core(jax.tree.map(lambda y: y[0], m),
                                     r[0], ln[0])
                    return jax.tree.map(lambda y: y[None], out)

                out = jax.shard_map(
                    block, mesh=mesh,
                    in_specs=(P("shard"), P("shard"), P("shard")),
                    out_specs=P("shard"), check_vma=False)(margs, *rargs)
            else:
                out = jax.vmap(align_core)(margs, *rargs)
            return jax.tree.map(
                lambda y: y.reshape((bs * s,) + y.shape[2:])[:b], out)

        self._align = jax.jit(
            align_stage_sharded if align_sharded else align_stage)
        self._align_stage_name = ("align_shard" if align_sharded
                                  else "align")
        # packed (distance, origin, tile) argmin-reduce on device
        self._merge = jax.jit(shard_merge.merge_graph)
        # the argmin collapses the shard axis but leaves its outputs
        # replicated across the mesh; a full-batch align traced on
        # replicated operands re-runs on every device, so the tiny
        # merged rows are committed to one device first.  A mesh-split
        # align partitions the work itself and must see mesh-addressable
        # inputs, so it keeps them replicated.
        self._off_mesh = (None if mesh is None or align_sharded
                          else mesh.devices.flat[0])
        self.last_stats: dict = {}
        # (stage, t0, t1, attrs) monotonic windows from the last call —
        # the serve engine replays them as child spans of its flush span
        self.last_times: list[tuple[str, float, float, dict]] = []

    def _stage_for(self, n_cap: int):
        fn = self._stages.get(n_cap)
        if fn is None:
            fn = self._stages[n_cap] = self._make_stage(n_cap)
        return fn

    @staticmethod
    def merge_host(st: CandidateStageResult) -> CandidateStageResult:
        """Reference host merge: lex ``(distance, origin, tile)`` per read.

        Kept as the independently coded oracle for the differential
        suite — the packed-key device merge must match it bit for bit.
        Identical windows duplicated across neighbouring shards'
        overlap regions collapse because their full sort key (and the
        window bytes behind it) are equal.
        """
        d = np.asarray(st.distance)
        origin = np.asarray(st.origin)
        tile = np.asarray(st.tile)
        dm = d.min(axis=0, keepdims=True)
        om = np.where(d == dm, origin, POS_SENTINEL)
        omin = om.min(axis=0, keepdims=True)
        tm = np.where(om == omin, tile, POS_SENTINEL)
        win = tm.argmin(axis=0)
        cols = np.arange(d.shape[1])
        pick = lambda a: np.asarray(a)[win, cols]  # noqa: E731
        return CandidateStageResult(
            distance=pick(st.distance), origin=pick(st.origin),
            tile=pick(st.tile), gwin=pick(st.gwin), bwin=pick(st.bwin),
            t_len=pick(st.t_len), prefilter_ok=pick(st.prefilter_ok))

    # chaos drills and older callers used ``ex.merge``
    merge = merge_host

    def merge_device(self, st: CandidateStageResult
                     ) -> CandidateStageResult:
        """Packed-key argmin-reduce on device (`repro.shard.merge`).

        Same winner and tie-break as `merge_host` — dead candidates
        carry sentinel origin *and* tile (the stage's shared ``live``
        mask), so the packed order and the three-level masked merge
        agree — with no host round trip.
        """
        with shard_merge.x64_scope():
            d, origin, tile, gwin, bwin, t_len, pf_ok, _win = self._merge(
                st.distance, st.origin, st.tile, st.gwin, st.bwin,
                st.t_len, st.prefilter_ok)
        out = CandidateStageResult(
            distance=d, origin=origin, tile=tile, gwin=gwin, bwin=bwin,
            t_len=t_len, prefilter_ok=pf_ok)
        if self._off_mesh is not None:
            out = jax.device_put(out, self._off_mesh)
        return out

    def start(self, arrays: GraphShardArrays, reads, read_lens, *,
              timed: bool = True) -> PendingBatch:
        """Dispatch screen → scatter → device merge → align, non-blocking.

        The prefilter's host sync (rung selection needs the survivor
        counts) always happens; everything after it stays on device
        until `finish`.  ``timed=False`` skips the inter-stage syncs
        for pipelined serving.  The zero-survivor short-circuit returns
        an already-materialized batch (``tail=None``).
        """
        reads = jnp.asarray(reads)
        lens = jnp.asarray(read_lens, jnp.int32)
        b = int(reads.shape[0])
        slots = b * self.shard_candidates
        c_pf = ("prefilter",) not in self._compiled
        t0 = time.monotonic()
        pf = self._pf(*arrays, reads, lens)  # leaves [S, B, ...]
        n_keep = np.asarray(pf.n_keep)  # [S, B]; host sync ends prefilter
        t1 = time.monotonic()
        kept = int(n_keep.sum())
        live = int(np.asarray(pf.n_live).sum())
        # one rung for all shards: the worst shard's survivor count
        n_cap = tile_rung(int(n_keep.sum(axis=1).max()), slots)
        stats = dict(
            candidate_slots=self.num_shards * slots, tiles_live=live,
            tiles_kept=kept, tiles_pruned=live - kept,
            dc_rows=self.num_shards * n_cap,
            dc_rows_dense=self.num_shards * slots,
            reads_zero_survivor=int((n_keep.sum(axis=0) == 0).sum()))
        self.last_stats = stats
        times = [("prefilter", t0, t1, {"compile": c_pf,
                                        "shards": self.num_shards})]
        if n_cap == 0:
            res = jax.tree_util.tree_map(
                np.asarray, unmapped_result(b, cfg=self.cfg,
                                            p_cap=self.p_cap))
            return PendingBatch(res=res, times=tuple(times), t_dispatch=t1,
                                tail=None, stats=stats)
        c_dc = (n_cap,) not in self._compiled
        c_al = (self._align_stage_name,) not in self._compiled
        t2 = time.monotonic()
        st = self._stage_for(n_cap)(*arrays, reads, lens, pf)
        if timed:
            jax.block_until_ready(st)
            t3 = time.monotonic()
            times.append(("dc_filter", t2, t3,
                          {"compile": c_dc,
                           "dc_rows": self.num_shards * n_cap}))
        merged = self.merge_device(st)
        if timed:
            jax.block_until_ready(merged.distance)
            t4 = time.monotonic()
            times.append(("merge_device", t3, t4,
                          {"shards": self.num_shards}))
        else:
            t4 = time.monotonic()
        res = self._align(merged, reads, lens)
        return PendingBatch(res=res, times=tuple(times), t_dispatch=t4,
                            tail=(self._align_stage_name,
                                  {"compile": c_al,
                                   "sharded": self.align_sharded}),
                            stats=stats)

    @staticmethod
    def finish(pending: PendingBatch):
        """Materialize a `start` batch → ``(numpy result, stage times)``."""
        if pending.tail is None:
            return pending.res, pending.times
        res = jax.tree_util.tree_map(np.asarray, pending.res)
        name, attrs = pending.tail
        return res, pending.times + ((name, pending.t_dispatch,
                                      time.monotonic(), attrs),)

    def __call__(self, arrays: GraphShardArrays, reads, read_lens
                 ) -> GraphMapResult:
        """Map one batch: screen → scatter → device merge → align."""
        res, times = self.finish(self.start(arrays, reads, read_lens))
        self.last_times = list(times)
        return res


# bounded LRU, mirroring shard.mapper: refresh() cycles must not leak
# compiled executors
_EXECUTORS: OrderedDict[tuple, ShardedGraphMapExecutor] = OrderedDict()
_EXECUTOR_CACHE_CAP = 8


def get_graph_executor(
    sharded: ShardedGraphIndex,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int = 256,
    filter_bits: int = 128,
    filter_k: int = 12,
    shard_candidates: int = 4,
    backend: str | None = None,
    block_bt: int | None = None,
    force_vmap: bool = False,
    prefilter: bool | None = None,
    align_sharded: bool = False,
) -> ShardedGraphMapExecutor:
    """Cached :class:`ShardedGraphMapExecutor` per (geometry, params)."""
    prefilter = _env_prefilter(prefilter)
    key = (sharded.layout_key, cfg, p_cap, filter_bits, filter_k,
           shard_candidates, backend, block_bt, force_vmap, prefilter,
           align_sharded)
    ex = _EXECUTORS.get(key)
    if ex is None:
        ex = ShardedGraphMapExecutor(
            sharded, cfg=cfg, p_cap=p_cap, filter_bits=filter_bits,
            filter_k=filter_k, shard_candidates=shard_candidates,
            backend=backend, block_bt=block_bt, force_vmap=force_vmap,
            prefilter=prefilter, align_sharded=align_sharded)
        _EXECUTORS[key] = ex
        while len(_EXECUTORS) > _EXECUTOR_CACHE_CAP:
            _EXECUTORS.popitem(last=False)
    else:
        _EXECUTORS.move_to_end(key)
    return ex


def map_batch_sharded_graph(
    sharded: ShardedGraphIndex,
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
    prefilter: bool | None = None,
    align_sharded: bool = False,
    pipelined: bool = False,
) -> GraphMapResult:
    """Map a read batch against a sharded variation-graph index.

    Returns the same :class:`repro.graph.mapper.GraphMapResult` (numpy
    leaves) as the single-device `graph.mapper.map_batch` —
    byte-identical positions, CIGARs, and GAF node paths for any shard
    count, with the q-gram tile screen on or off.  Executors are cached
    per (geometry, parameters).  ``pipelined`` dispatches through the
    non-blocking `start`/`finish` surface (no inter-stage syncs).
    """
    ex = get_graph_executor(
        sharded, cfg=cfg, p_cap=p_cap, filter_bits=filter_bits,
        filter_k=filter_k, shard_candidates=shard_candidates,
        backend=backend, block_bt=block_bt, force_vmap=force_vmap,
        prefilter=prefilter, align_sharded=align_sharded)
    if pipelined:
        res, times = ex.finish(ex.start(sharded.arrays, reads, read_lens,
                                        timed=False))
        ex.last_times = list(times)
        return res
    return ex(sharded.arrays, reads, read_lens)
