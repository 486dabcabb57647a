"""End-to-end sequence-to-graph read mapper (paper Figure 6-1, batched).

Seed-and-extend over a graph index, as a three-stage pipeline:

  * **Stage A — seed + tile pre-filter** (`tile_prefilter`): MinSeed
    minimizer seeding on the backbone, then a q-gram Bloom screen over
    each candidate tile (`core/filter` primitives against the index's
    per-tile ``tile_bloom``/``tile_slack``) — one vectorized count, no
    DC launch.  The screen is *sound*: by the q-gram lemma a tile whose
    confirmed q-gram count falls below ``(m-q+1) - q·k - slack`` cannot
    contain a mapping within ``filter_k`` edits, so every pruned slot's
    GenASM-DC distance would have been ``filter_k + 1`` anyway and the
    lexicographic winner is untouched (GAF output stays byte-identical
    with the screen on or off).
  * **Stage B — compacted gather + BitAlign-DC filter**
    (`graph_candidate_stage` with ``pf``/``n_cap``): survivors are
    argsort-compacted into a shared ``[n_cap]``-row buffer (``n_cap`` a
    `tile_rung` high-water bucket chosen on the host), the per-node
    GenASM-DC filter runs over those rows only — empty and pruned slots
    stop burning kernel lanes — and distances scatter back to the dense
    ``[B, max_candidates]`` grid for the unchanged shard-order-free
    winner rule ``min (distance, origin, tile)``.
  * **Stage C — align** (`align_winners`): windowed graph alignment of
    each read's winning window through `repro.align.align_batch`
    (``graph_lax`` / ``graph_pallas``), with failed reads canonicalized
    (``ops``=OP_PAD, ``n_ops``=0) so an all-pruned batch can skip the
    launch entirely (`unmapped_result`) without changing any output.

The candidate stage is written against a :class:`GraphView` — local
graph text, screen and backbone slices plus the global offsets of their
first rows — so the whole-graph mapper and the sharded mapper
(`repro.shard.graph_mapper`) run the *same* seeding/screen/filter/
selection code: per-candidate distances, refined anchors, and window
bytes are bit-identical at 1 and N shards.

`map_batch` is **host-orchestrated** (it syncs the survivor count
between stages to pick the rung) — do not wrap it in ``jax.jit``; the
stages are jitted internally and cached per geometry + rung.
"""
from __future__ import annotations

import os
import time
from collections import OrderedDict
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import filter as qfilter
from repro.core.bitvector import WILDCARD
from repro.core.genasm import GenASMConfig
from repro.core.genasm_tb import OP_PAD
from repro.core.mapper import POS_SENTINEL
from repro.core.segram.graph import HOP_LIMIT
from repro.core.segram.minimizer import seed_candidates

from .index import GraphArrays, GraphIndex, gather_windows
from .windowed import bitalign_search, unpack_graph_text

# linear backend names map to their graph twins so ``backend="auto"`` (or
# an engine configured with a linear name) serves the graph workload on
# the matching implementation tier
_GRAPH_TWIN = {"lax": "graph_lax", "ref": "graph_lax",
               "pallas_dc": "graph_pallas", "pallas_dc_v2": "graph_pallas"}


def graph_backend_name(backend: str | None = None) -> str:
    """Resolve a backend name (or None/"auto") to a graph backend."""
    from repro import align as align_dispatch

    name = align_dispatch.resolve_backend(backend).name
    return _GRAPH_TWIN.get(name, name)


class GraphMapResult(NamedTuple):
    """Batched graph-mapping outcome (the GAF-row payload).

    ``position``/``distance`` are ``-1`` for unmapped reads; ``path``
    holds global node ids per CIGAR op (``-1`` for insertions/padding).
    Failed reads are canonical: ``ops`` all OP_PAD, ``n_ops`` 0.
    """

    position: jnp.ndarray  # int32 backbone coord of first aligned node (-1)
    distance: jnp.ndarray  # int32 edit distance (-1 if unmapped)
    ops: jnp.ndarray  # packed CIGAR
    n_ops: jnp.ndarray
    path: jnp.ndarray  # [B, cap] int32 global node ids per op (-1 for I/pad)
    failed: jnp.ndarray


class GraphView(NamedTuple):
    """One shard's (or the whole graph's) view of a graph index.

    Local array slices plus the global coordinate of each slice's first
    row; the whole-graph view has all offsets 0.  ``idx_positions`` stay
    *global* backbone coordinates in every view — merging per-shard
    candidates then needs no translation step.
    """

    gtext: jnp.ndarray  # [Nb] uint32 local packed graph text slice
    tile_base: jnp.ndarray  # int32 global tile id of local tile row 0
    node_of_backbone: jnp.ndarray  # [Lb] int32 local backbone→node slice
    nb_offset: jnp.ndarray  # int32 global backbone coord of slice row 0
    backbone: jnp.ndarray  # [Nb] int32 local node→backbone slice
    node_base: jnp.ndarray  # int32 global node id of gtext/backbone row 0
    idx_hashes: jnp.ndarray  # [M] uint32 sorted minimizer hashes
    idx_positions: jnp.ndarray  # [M] int32 GLOBAL backbone positions
    tile_bloom: jnp.ndarray  # [Ct, BLOOM_WORDS] uint32 per-tile q-gram Bloom
    tile_slack: jnp.ndarray  # [Ct] int32 per-tile q-gram-lemma slack


def whole_graph_view(garr: GraphArrays) -> GraphView:
    """The trivial single-shard view: full arrays, zero offsets."""
    zero = jnp.int32(0)
    return GraphView(
        gtext=garr.gtext, tile_base=zero,
        node_of_backbone=garr.node_of_backbone,
        nb_offset=zero, backbone=garr.backbone, node_base=zero,
        idx_hashes=garr.idx_hashes, idx_positions=garr.idx_positions,
        tile_bloom=garr.tile_bloom, tile_slack=garr.tile_slack)


class CandidateStageResult(NamedTuple):
    """Per-read winner of one view's seeding + GenASM-DC filter stage.

    Everything downstream alignment needs travels with the winner, so
    the align stage never touches the (possibly remote) graph arrays:
    ``gwin`` is the packed ``[B, t_cap]`` graph text window, ``bwin``
    the backbone coordinate of each window node (``-1`` on alt nodes).
    """

    distance: jnp.ndarray  # [B] int32 filter distance (filter_k+1 = none)
    origin: jnp.ndarray  # [B] int32 global node id of window node 0
    tile: jnp.ndarray  # [B] int32 global winning tile id
    gwin: jnp.ndarray  # [B, t_cap] uint32 packed graph text window
    bwin: jnp.ndarray  # [B, t_cap] int32 backbone coord per window node
    t_len: jnp.ndarray  # [B] int32 valid window length
    prefilter_ok: jnp.ndarray  # [B] bool


class TilePrefilterResult(NamedTuple):
    """Stage-A output: seeds plus the per-slot tile-screen verdict."""

    starts: jnp.ndarray  # [B, C] int32 candidate backbone starts
    votes: jnp.ndarray  # [B, C] int32 seed votes (0 = dead slot)
    keep: jnp.ndarray  # [B, C] bool live & screen-pass (survivors)
    n_keep: jnp.ndarray  # [B] int32 survivors per read
    n_live: jnp.ndarray  # [B] int32 live (seeded) slots per read


def tile_rung(n: int, cap: int) -> int:
    """High-water bucket for the compacted DC row count.

    The smallest power of two ≥ max(n, 8), clamped to the dense slot
    count ``cap`` — the (read-length, tile-count) bucket ladder's second
    axis.  0 survivors → rung 0 (callers short-circuit).
    """
    if n <= 0:
        return 0
    r = 8
    while r < n:
        r *= 2
    return min(r, cap)


def _seed(view: GraphView, reads, *, max_candidates: int, minimizer_w: int,
          minimizer_k: int):
    """MinSeed over the view's minimizer table: [B, C] starts + votes."""
    seed_fn = partial(seed_candidates, w=minimizer_w, k=minimizer_k,
                      max_candidates=max_candidates)
    return jax.vmap(
        lambda r: seed_fn(r, view.idx_hashes, view.idx_positions))(reads)


def _tiles_of_starts(view: GraphView, starts, *, tile_stride: int,
                     n_tiles: int, backbone_len: int):
    """Candidate backbone starts → (global tile id, local tile row)."""
    sb = jnp.clip(starts - HOP_LIMIT, 0, backbone_len - 1)
    nb_len = view.node_of_backbone.shape[0]
    node = view.node_of_backbone[
        jnp.clip(sb - view.nb_offset, 0, nb_len - 1)]  # [B, C] global ids
    tile_g = jnp.clip(node // tile_stride, 0, n_tiles - 1)
    n_local_tiles = view.tile_slack.shape[0]
    tile_local = jnp.clip(tile_g - view.tile_base, 0, n_local_tiles - 1)
    return tile_g, tile_local


def _filter_pattern(reads, read_lens, filter_bits: int):
    """Wildcard-masked [B, fb] filter pattern + clamped lengths."""
    fb = filter_bits
    fpat = jnp.where(
        jnp.arange(fb)[None, :] < jnp.minimum(read_lens, fb)[:, None],
        reads[:, :fb], WILDCARD).astype(jnp.int8)
    return fpat, jnp.minimum(read_lens, fb)


def tile_prefilter(
    view: GraphView,
    reads: jnp.ndarray,
    read_lens: jnp.ndarray,
    *,
    tile_stride: int,
    n_tiles: int,
    backbone_len: int,
    filter_bits: int,
    filter_k: int,
    max_candidates: int,
    minimizer_w: int,
    minimizer_k: int,
    prefilter: bool = True,
) -> TilePrefilterResult:
    """Stage A: seed, then screen each candidate tile without any DC.

    A slot survives iff it is live (has seed votes) and its tile's Bloom
    filter confirms at least ``(m-q+1) - q·filter_k - tile_slack`` of
    the read's q-grams (`core/filter.qgram_min_hits`) — the q-gram-lemma
    bound under which a ≤ ``filter_k`` mapping could exist.  With
    ``prefilter=False`` the screen is skipped (survivor = live), which
    still compacts away dead slots downstream.
    """
    read_lens = read_lens.astype(jnp.int32)
    starts, votes = _seed(view, reads, max_candidates=max_candidates,
                          minimizer_w=minimizer_w, minimizer_k=minimizer_k)
    live = votes > 0
    if prefilter:
        _, tile_local = _tiles_of_starts(
            view, starts, tile_stride=tile_stride, n_tiles=n_tiles,
            backbone_len=backbone_len)
        fpat, flens = _filter_pattern(reads, read_lens, filter_bits)
        codes = jax.vmap(qfilter.qgram_codes)(fpat)  # [B, fb-q+1]
        b, c = votes.shape
        p = codes.shape[-1]
        n_pos = jnp.maximum(flens - (qfilter.QGRAM_Q - 1), 0)  # [B]
        pos_ok = jnp.arange(p)[None, :] < n_pos[:, None]
        hits = qfilter.qgram_hits(
            jnp.broadcast_to(codes[:, None, :], (b, c, p)),
            jnp.broadcast_to(pos_ok[:, None, :], (b, c, p)),
            view.tile_bloom[tile_local])  # [B, C]
        need = qfilter.qgram_min_hits(n_pos[:, None], filter_k,
                                      view.tile_slack[tile_local])
        keep = live & (hits >= need)
    else:
        keep = live
    return TilePrefilterResult(
        starts=starts, votes=votes, keep=keep,
        n_keep=jnp.sum(keep, axis=-1, dtype=jnp.int32),
        n_live=jnp.sum(live, axis=-1, dtype=jnp.int32))


def _filter_best(wins_flat, fpat_flat, flens_flat, *, span: int,
                 m_bits: int, k: int, use_kernel: bool,
                 block_bt: int | None, interpret: bool):
    """Per-row ``(d, off)``: the minimum filter distance over the first
    ``span`` window nodes and the first node attaining it (``k + 1`` and
    0 when none matches) — kernel or pure-lax path, bitwise equal."""
    bases, succ = unpack_graph_text(wins_flat)
    if use_kernel:
        from repro.kernels.bitalign import bitalign_filter_batch

        return bitalign_filter_batch(
            bases, succ, fpat_flat, flens_flat, m_bits=m_bits, k=k,
            span=span, block_bt=block_bt or 128, interpret=interpret)
    f = partial(bitalign_search, m_bits=m_bits, k=k)
    dists = jax.vmap(f)(bases, succ, fpat_flat, flens_flat)
    dists = jnp.where(jnp.arange(dists.shape[-1]) < span, dists, k + 1)
    return (jnp.min(dists, axis=-1).astype(jnp.int32),
            jnp.argmin(dists, axis=-1).astype(jnp.int32))


def graph_candidate_stage(
    view: GraphView,
    reads: jnp.ndarray,
    read_lens: jnp.ndarray,
    *,
    tile_stride: int,
    n_tiles: int,
    backbone_len: int,
    n_nodes: int,
    t_cap: int,
    filter_bits: int,
    filter_k: int,
    max_candidates: int,
    minimizer_w: int,
    minimizer_k: int,
    margin: int,
    use_kernel: bool = False,
    block_bt: int | None = None,
    interpret: bool = True,
    pf: TilePrefilterResult | None = None,
    n_cap: int | None = None,
) -> CandidateStageResult:
    """Seed, gather, filter, and select one view's best candidate per read.

    ``reads`` is ``[B, p_cap] int8`` with ``read_lens [B] int32`` valid
    lengths; ``n_tiles``/``backbone_len``/``n_nodes`` are the *global*
    graph sizes (the view's local arrays may be smaller slices).  The
    per-read winner minimizes ``(filter distance, origin node, tile)``
    lexicographically, so merging the winners of disjoint views
    reproduces the whole-graph winner exactly.

    Each slot's window is gathered from the view's graph text at its
    tile's first node, ``t_cap + tile_stride + margin`` nodes long: the
    filter refines the anchor over the first ``tile_stride + margin``
    nodes, and ``t_cap`` nodes of alignment text follow any anchor.

    With ``pf`` (a `tile_prefilter` result) the DC filter only scores
    surviving slots; with ``n_cap`` additionally set (a `tile_rung`
    bucket) survivors are compacted into an ``[n_cap]``-row buffer so
    pruned and dead slots launch no DC lanes at all.  Both modes are
    bitwise-identical to the dense legacy path (``pf=None``) on every
    mapped read: pruned slots take the exact ``(filter_k+1, off=0)``
    values the dense scan computes for them.
    """
    b = reads.shape[0]
    c = max_candidates
    search_span = tile_stride + margin
    win_len = t_cap + search_span
    read_lens = read_lens.astype(jnp.int32)

    def windows(tile_local):
        start = (view.tile_base + tile_local) * tile_stride
        return gather_windows(view.gtext, start, win_len, n_nodes=n_nodes,
                              node_base=view.node_base)

    if pf is None:
        starts, votes = _seed(view, reads, max_candidates=c,
                              minimizer_w=minimizer_w,
                              minimizer_k=minimizer_k)
        keep = votes > 0
    else:
        starts, votes, keep = pf.starts, pf.votes, pf.keep
    tile_g, tile_local = _tiles_of_starts(
        view, starts, tile_stride=tile_stride, n_tiles=n_tiles,
        backbone_len=backbone_len)
    fpat, flens = _filter_pattern(reads, read_lens, filter_bits)
    # anchors past the search span could not fit an alignment window
    dc = partial(_filter_best, span=search_span, m_bits=filter_bits,
                 k=filter_k, use_kernel=use_kernel, block_bt=block_bt,
                 interpret=interpret)

    if n_cap is None:
        # --- dense: one gather + one DC launch over every slot
        wins = windows(tile_local)  # [B, C, win_len]
        d_c, off_c = dc(wins.reshape(b * c, win_len),
                        jnp.repeat(fpat, c, axis=0), jnp.repeat(flens, c))
        d_c = jnp.where(keep, d_c.reshape(b, c), filter_k + 1)
        off_c = off_c.reshape(b, c)
    else:
        # --- ragged: compact survivors into [n_cap] rows, DC those only,
        # scatter back to the dense grid.  Non-survivor slots take the
        # (filter_k+1, off=0) values the dense scan computes for them:
        # dead slots are masked there, and screen-pruned slots provably
        # have every in-span distance at filter_k+1 (argmin 0).
        bc = b * c
        kf = keep.reshape(bc)
        order = jnp.argsort(
            jnp.where(kf, 0, bc).astype(jnp.int32)
            + jnp.arange(bc, dtype=jnp.int32))
        slots = order[:n_cap]  # survivors first, in slot order; distinct
        n_tot = jnp.sum(kf, dtype=jnp.int32)
        rowmask = jnp.arange(n_cap) < n_tot
        ridx = slots // c  # read of each compacted row
        wins_r = windows(tile_local.reshape(bc)[slots])
        d_r, off_r = dc(wins_r, fpat[ridx], flens[ridx])  # [n_cap] each
        d_c = jnp.full((bc,), filter_k + 1, jnp.int32).at[slots].set(
            jnp.where(rowmask, d_r, filter_k + 1)).reshape(b, c)
        off_c = jnp.zeros((bc,), jnp.int32).at[slots].set(
            jnp.where(rowmask, off_r, 0)).reshape(b, c)

    live = votes > 0
    origin_c = jnp.where(live, tile_g * tile_stride + off_c, POS_SENTINEL)
    tile_m = jnp.where(live, tile_g, POS_SENTINEL)

    # --- lexicographic winner per read: min (distance, origin, tile)
    dm = jnp.min(d_c, axis=-1, keepdims=True)
    om = jnp.where(d_c == dm, origin_c, POS_SENTINEL)
    omin = jnp.min(om, axis=-1, keepdims=True)
    tm = jnp.where(om == omin, tile_m, POS_SENTINEL)
    ci = jnp.argmin(tm, axis=-1)  # [B]

    rows = jnp.arange(b)
    d_best = d_c[rows, ci]
    origin = origin_c[rows, ci]
    tile_best = tile_g[rows, ci]
    off = off_c[rows, ci]
    prefilter_ok = d_best <= filter_k

    # --- slice the anchored alignment window out of the winning window
    win_start = (view.tile_base + tile_local[rows, ci]) * tile_stride
    gwin = jax.vmap(
        lambda wbuf, o: jax.lax.dynamic_slice(wbuf, (o,), (t_cap,)))(
        windows(tile_local[rows, ci]), off)
    t_len = jnp.clip(n_nodes - win_start - off, 0, t_cap)

    # backbone coordinate of every window node, shipped with the window
    # so the align stage needs no graph arrays (clip mirrors the
    # whole-graph gather: nodes past the graph end read backbone[n-1])
    bb_len = view.backbone.shape[0]
    widx = origin[:, None] + jnp.arange(t_cap)[None, :]
    bwin = view.backbone[jnp.clip(widx - view.node_base, 0, bb_len - 1)]
    return CandidateStageResult(
        distance=d_best.astype(jnp.int32), origin=origin,
        tile=jnp.where(live[rows, ci], tile_best, POS_SENTINEL),
        gwin=gwin, bwin=bwin.astype(jnp.int32),
        t_len=t_len.astype(jnp.int32), prefilter_ok=prefilter_ok)


def align_winners(
    stage: CandidateStageResult,
    reads: jnp.ndarray,
    read_lens: jnp.ndarray,
    *,
    cfg: GenASMConfig,
    p_cap: int,
    backend: str,
    block_bt: int | None = None,
) -> GraphMapResult:
    """Align the per-read winning windows and translate paths to GAF terms.

    ``stage`` is a (possibly merged) :class:`CandidateStageResult`;
    windows are ``[B, t_cap]`` packed graph text and ``bwin`` carries
    the backbone coordinates, so this runs without the graph index —
    the "single batched align_batch call" of the sharded design.

    Failed reads come out canonical (``ops`` all OP_PAD, ``n_ops`` 0, and
    position/distance/path already ``-1``): different executions may feed
    different garbage windows for reads with no surviving candidate, and
    canonicalizing here is what keeps prefilter on/off — and the
    zero-survivor `unmapped_result` short-circuit — bitwise identical.
    """
    from repro import align as align_dispatch

    read_lens = read_lens.astype(jnp.int32)
    t_cap = stage.gwin.shape[-1]
    pat = jnp.where(jnp.arange(p_cap)[None, :] < read_lens[:, None],
                    reads[:, :p_cap], WILDCARD).astype(jnp.int8)
    res = align_dispatch.align_batch(
        stage.gwin, pat, read_lens, stage.t_len, cfg=cfg, backend=backend,
        p_cap=p_cap, block_bt=block_bt)

    # window-relative node offsets -> global path -> backbone position
    rows = jnp.arange(stage.gwin.shape[0])
    live = res.nodes >= 0
    path = jnp.where(live, res.nodes + stage.origin[:, None], -1)
    bpath = jnp.where(
        live,
        jnp.take_along_axis(stage.bwin, jnp.clip(res.nodes, 0, t_cap - 1),
                            axis=-1), -1)
    first = jnp.argmax(bpath >= 0, axis=-1)  # first backbone node on path
    pos = bpath[rows, first]
    failed = res.failed | (~stage.prefilter_ok)
    return GraphMapResult(
        position=jnp.where(failed, -1, pos).astype(jnp.int32),
        distance=jnp.where(failed, -1, res.distance),
        ops=jnp.where(failed[:, None], jnp.asarray(OP_PAD, res.ops.dtype),
                      res.ops),
        n_ops=jnp.where(failed, 0, res.n_ops),
        path=jnp.where(failed[:, None], -1, path),
        failed=failed,
    )


def unmapped_result(b: int, *, cfg: GenASMConfig, p_cap: int
                    ) -> GraphMapResult:
    """The canonical all-failed batch: what `align_winners` emits for a
    failed read, at the ops/path widths an align launch would produce —
    the zero-survivor short-circuit returns this without any DC/align."""
    cap = cfg.ops_cap(p_cap)
    return GraphMapResult(
        position=jnp.full((b,), -1, jnp.int32),
        distance=jnp.full((b,), -1, jnp.int32),
        ops=jnp.full((b, cap), OP_PAD, jnp.int8),
        n_ops=jnp.zeros((b,), jnp.int32),
        path=jnp.full((b, cap), -1, jnp.int32),
        failed=jnp.ones((b,), bool))


def _env_prefilter(prefilter: bool | None) -> bool:
    """None → the REPRO_GRAPH_PREFILTER env default (on unless "0")."""
    if prefilter is None:
        return os.environ.get("REPRO_GRAPH_PREFILTER", "1") != "0"
    return bool(prefilter)


class GraphMapExecutor:
    """Host-orchestrated three-stage graph mapper for one static geometry.

    Stage A (jitted once) seeds and screens — no DC.  A host sync on the
    survivor counts picks the `tile_rung`; stage B (jitted once per
    rung) compacts survivors, runs BitAlign-DC over ``n_cap`` rows only,
    and selects winners; stage C (jitted once) aligns them.  An
    all-pruned batch skips B and C entirely (`unmapped_result`).

    ``trace_hook`` (if given) is called with a hashable stage key at
    trace time — ``("prefilter",)``, ``(n_cap,)`` per rung, and
    ``("align",)`` — so tests can assert one compile per ladder rung.
    ``last_stats`` holds the previous call's pruning/occupancy counters
    (the serve engine forwards them into its metrics registry).
    """

    def __init__(self, *, geometry: tuple[int, int, int],
                 cfg: GenASMConfig = GenASMConfig(),
                 p_cap: int = 256,
                 filter_bits: int = 128,
                 filter_k: int = 12,
                 max_candidates: int = 4,
                 minimizer_w: int = 10,
                 minimizer_k: int = 15,
                 backend: str | None = None,
                 block_bt: int | None = None,
                 prefilter: bool | None = None,
                 trace_hook=None):
        from repro import align as align_dispatch

        if filter_bits % 32:
            raise ValueError(f"filter_bits must be a multiple of 32, got "
                             f"{filter_bits}")
        self.backend = graph_backend_name(backend)
        use_kernel = align_dispatch.get_backend(self.backend).uses_pallas
        interpret = align_dispatch.needs_interpret()
        self.cfg = cfg
        self.p_cap = p_cap
        self.t_cap = p_cap + 2 * cfg.w
        tile_stride, margin, window = geometry
        if window < self.t_cap:
            raise ValueError(
                f"index window {window} < the {self.t_cap}-node alignment "
                f"text at p_cap {p_cap}: the tile screen would not cover "
                f"the gathered windows; rebuild the index with window >= "
                f"{self.t_cap}")
        self.tile_stride = tile_stride
        self.max_candidates = max_candidates
        self.prefilter = _env_prefilter(prefilter)
        user_hook = trace_hook or (lambda key: None)
        self._compiled: set = set()  # stage keys that have traced

        def hook(key):
            self._compiled.add(key)
            user_hook(key)

        self._hook = hook
        fbits = min(filter_bits, p_cap)
        self._pf_kw = dict(
            tile_stride=tile_stride, filter_bits=fbits, filter_k=filter_k,
            max_candidates=max_candidates, minimizer_w=minimizer_w,
            minimizer_k=minimizer_k, prefilter=self.prefilter)
        self._stage_kw = dict(
            tile_stride=tile_stride, margin=margin, t_cap=self.t_cap,
            filter_bits=fbits, filter_k=filter_k,
            max_candidates=max_candidates,
            minimizer_w=minimizer_w, minimizer_k=minimizer_k,
            use_kernel=use_kernel, block_bt=block_bt, interpret=interpret)

        def pf_fn(garr, reads, lens):
            self._hook(("prefilter",))
            return tile_prefilter(
                whole_graph_view(garr), reads, lens,
                n_tiles=garr.tile_slack.shape[0],
                backbone_len=garr.node_of_backbone.shape[0], **self._pf_kw)

        self._pf = jax.jit(pf_fn)
        self._stages: dict[int, object] = {}

        def align_fn(st, reads, lens):
            self._hook(("align",))
            return align_winners(st, reads, lens, cfg=cfg, p_cap=p_cap,
                                 backend=self.backend, block_bt=block_bt)

        self._align = jax.jit(align_fn)
        self.last_stats: dict = {}
        # (stage, t0, t1, attrs) monotonic windows from the last call —
        # the serve engine replays them as child spans of its flush span
        self.last_times: list[tuple[str, float, float, dict]] = []

    def _stage(self, n_cap: int):
        fn = self._stages.get(n_cap)
        if fn is None:
            def stage_fn(garr, reads, lens, pf, _n=n_cap):
                self._hook((_n,))
                return graph_candidate_stage(
                    whole_graph_view(garr), reads, lens,
                    n_tiles=garr.tile_slack.shape[0],
                    backbone_len=garr.node_of_backbone.shape[0],
                    n_nodes=garr.gtext.shape[0], pf=pf, n_cap=_n,
                    **self._stage_kw)

            fn = self._stages[n_cap] = jax.jit(stage_fn)
        return fn

    def __call__(self, garr: GraphArrays, reads, read_lens) -> GraphMapResult:
        reads = jnp.asarray(reads)
        lens = jnp.asarray(read_lens, jnp.int32)
        b = reads.shape[0]
        slots = b * self.max_candidates
        c_pf = ("prefilter",) not in self._compiled
        t0 = time.monotonic()
        pf = self._pf(garr, reads, lens)
        n_keep = np.asarray(pf.n_keep)  # host sync ends the prefilter stage
        t1 = time.monotonic()
        total = int(n_keep.sum())
        live = int(np.asarray(pf.n_live).sum())
        n_cap = tile_rung(total, slots)
        self.last_stats = dict(
            candidate_slots=slots, tiles_live=live, tiles_kept=total,
            tiles_pruned=live - total, dc_rows=n_cap, dc_rows_dense=slots,
            reads_zero_survivor=int((n_keep == 0).sum()))
        self.last_times = [("prefilter", t0, t1, {"compile": c_pf})]
        if total == 0:
            return unmapped_result(b, cfg=self.cfg, p_cap=self.p_cap)
        c_dc = (n_cap,) not in self._compiled
        c_al = ("align",) not in self._compiled
        t2 = time.monotonic()
        st = self._stage(n_cap)(garr, reads, lens, pf)
        jax.block_until_ready(st)
        t3 = time.monotonic()
        res = self._align(st, reads, lens)
        jax.block_until_ready(res)
        t4 = time.monotonic()
        self.last_times += [
            ("dc_filter", t2, t3, {"compile": c_dc, "dc_rows": n_cap}),
            ("align", t3, t4, {"compile": c_al})]
        return res


# bounded LRU over map_batch's statics: refresh()/sweep loops must not
# leak compiled stage ladders
_EXECUTORS: OrderedDict[tuple, GraphMapExecutor] = OrderedDict()
_EXECUTOR_CACHE_CAP = 8


def get_map_executor(**kw) -> GraphMapExecutor:
    """Cached :class:`GraphMapExecutor` per static-parameter set."""
    kw["prefilter"] = _env_prefilter(kw.get("prefilter"))
    key = tuple(sorted(kw.items()))
    ex = _EXECUTORS.get(key)
    if ex is None:
        ex = GraphMapExecutor(**kw)
        _EXECUTORS[key] = ex
        while len(_EXECUTORS) > _EXECUTOR_CACHE_CAP:
            _EXECUTORS.popitem(last=False)
    else:
        _EXECUTORS.move_to_end(key)
    return ex


def map_batch(
    garr: GraphArrays,
    reads: jnp.ndarray,
    read_lens: jnp.ndarray,
    *,
    geometry: tuple[int, int, int],
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int = 256,
    filter_bits: int = 128,
    filter_k: int = 12,
    max_candidates: int = 4,
    minimizer_w: int = 10,
    minimizer_k: int = 15,
    backend: str | None = None,
    block_bt: int | None = None,
    prefilter: bool | None = None,
) -> GraphMapResult:
    """Map a read batch against the graph index.

    ``garr`` is the device half of a `GraphIndex` whose ``geometry``
    (tile stride, margin, window) the caller passes statically: it shapes
    the tile→node arithmetic and the gathered windows, and the mapper
    checks that the index's window covers what it gathers.
    ``backend`` resolves through `repro.align` with linear names mapped
    to their graph twins.  ``prefilter`` toggles the q-gram tile screen
    (None → the ``REPRO_GRAPH_PREFILTER`` env default, on); results are
    bitwise identical either way — the screen only removes tiles that
    lose the lexicographic merge regardless.

    Host-orchestrated (three jitted stages around a survivor-count
    sync): call it eagerly, do **not** wrap it in ``jax.jit``.
    """
    ex = get_map_executor(
        geometry=geometry, cfg=cfg, p_cap=p_cap, filter_bits=filter_bits,
        filter_k=filter_k, max_candidates=max_candidates,
        minimizer_w=minimizer_w, minimizer_k=minimizer_k, backend=backend,
        block_bt=block_bt, prefilter=prefilter)
    return ex(garr, reads, read_lens)


def map_batch_index(gidx: GraphIndex, reads, read_lens, **kw
                    ) -> GraphMapResult:
    """`map_batch` with the geometry pulled off a host `GraphIndex`."""
    kw.setdefault("minimizer_w", gidx.minimizer_w)
    kw.setdefault("minimizer_k", gidx.minimizer_k)
    return map_batch(gidx.arrays, reads, read_lens, geometry=gidx.geometry,
                     **kw)
