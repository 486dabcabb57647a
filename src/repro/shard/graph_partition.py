"""Variation-graph sharding: per-device tile ranges + backbone slices.

The graph twin of `partition.py` (SeGraM §6.5: each channel owns the
sub-graph backing its slice of the linear backbone).  A shard owns a
contiguous *backbone* core range; from it we derive, by pure slicing of
the already-built global `repro.graph.index.GraphIndex` arrays:

* the minimizer-table entries whose (global) backbone positions fall in
  the core;
* a haloed ``node_of_backbone`` slice (candidate backbone coordinate →
  node id);
* the contiguous global **tile** range those nodes map to under
  ``node // tile_stride``, with the tiles' screen rows (Bloom, slack);
* the node range those tiles cover, sliced out of the one packed graph
  text and out of ``backbone`` (node → backbone coordinate): windows are
  gathered from the slice under global node ids and cut at their own
  ends by the shared rule, so their bytes are bit-identical to the
  whole-graph index's, and the merged winner's GAF path translates
  without touching any other shard.

Candidates stay in global coordinates end-to-end (global backbone
positions in the table, global tile ids, global origin node ids), so
the merge is a pure lexicographic min — no translation step.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.segram.graph import Variant
from repro.graph.index import EpochedGraphIndex, GraphIndex, build_graph_index
from repro.graph.windowed import SENT_NODE

from .partition import (DEFAULT_HALO, ShardLayout, _PAD_HASH, _PAD_POS,
                        plan_layout)


class GraphShardArrays(NamedTuple):
    """Device half of a sharded graph index, stacked ``[S, ...]``.

    Row ``i`` is shard ``i``; all ids/positions are global (tile ids via
    ``tile_base``, node ids via ``node_base``, backbone coordinates via
    ``nb_offset`` — each row's arrays are local slices whose first row
    sits at that global coordinate).
    """

    gtext: jnp.ndarray  # [S, Nb] uint32 packed graph text slice
    tile_base: jnp.ndarray  # [S] int32 global tile id of local row 0
    node_of_backbone: jnp.ndarray  # [S, Lb] int32 backbone→node slice
    nb_offset: jnp.ndarray  # [S] int32 global backbone coord of slice row 0
    backbone: jnp.ndarray  # [S, Nb] int32 node→backbone slice
    node_base: jnp.ndarray  # [S] int32 global node id of gtext/backbone row 0
    hashes: jnp.ndarray  # [S, Mm] uint32 sorted minimizer hashes
    positions: jnp.ndarray  # [S, Mm] int32 GLOBAL backbone positions
    tile_bloom: jnp.ndarray  # [S, Ct, BLOOM_WORDS] uint32 q-gram Blooms
    tile_slack: jnp.ndarray  # [S, Ct] int32 q-gram-lemma screen slack


@dataclass
class ShardedGraphIndex:
    """Host handle: stacked graph shards + the global geometry statics."""

    arrays: GraphShardArrays
    layout: ShardLayout
    ref: np.ndarray  # host reference copy (GAF tlen, refresh)
    tile_len: int
    tile_stride: int
    n_tiles: int  # global tile count
    n_nodes: int  # global linearized-graph node count
    minimizer_w: int
    minimizer_k: int
    window: int
    margin: int

    @property
    def num_shards(self) -> int:
        """Number of graph shards."""
        return self.layout.num_shards

    @property
    def ref_len(self) -> int:
        """Backbone (linear reference) length in bases."""
        return self.layout.ref_len

    @property
    def layout_key(self) -> tuple:
        """Hashable geometry key (partition + tile pitch + padded dims)."""
        a = self.arrays
        return (self.layout.bounds, self.layout.halo, self.tile_len,
                self.tile_stride, self.margin, self.n_nodes,
                int(a.tile_slack.shape[1]), int(a.node_of_backbone.shape[1]),
                int(a.backbone.shape[1]), int(a.hashes.shape[1]))


def shard_graph_index(gidx: GraphIndex, num_shards: int, *,
                      halo: int = DEFAULT_HALO) -> ShardedGraphIndex:
    """Slice a built ``GraphIndex`` into per-device shards.

    Pure slicing of the global arrays — graph text, screen rows, and
    minimizer entries are exactly the whole-graph ones, which is what
    keeps the sharded mapper's windows byte-identical to the single-device
    path.
    """
    a = gidx.arrays
    L = int(a.node_of_backbone.shape[0])
    n_tiles = gidx.n_tiles
    n_nodes = gidx.n_nodes
    layout = plan_layout(L, num_shards, halo)
    nob = np.asarray(a.node_of_backbone)
    g_hash = np.asarray(a.idx_hashes)
    g_pos = np.asarray(a.idx_positions)
    backbone = np.asarray(a.backbone)
    text = np.asarray(a.gtext)
    tbloom = np.asarray(a.tile_bloom)
    tslack = np.asarray(a.tile_slack)

    rows = []
    for i in range(num_shards):
        lo, hi = layout.core(i)
        blo, bhi = layout.slice_range(i)
        tlo = int(nob[blo]) // gidx.tile_stride
        thi = min(n_tiles, int(nob[bhi - 1]) // gidx.tile_stride + 1)
        node_lo = tlo * gidx.tile_stride
        node_hi = min(n_nodes, (thi - 1) * gidx.tile_stride + gidx.tile_len)
        m = (g_pos >= lo) & (g_pos < hi)
        rows.append(dict(
            tile_base=tlo, nob=nob[blo:bhi], nb_offset=blo,
            text=text[node_lo:node_hi], backbone=backbone[node_lo:node_hi],
            node_base=node_lo,
            hashes=g_hash[m], positions=g_pos[m],
            tbloom=tbloom[tlo:thi], tslack=tslack[tlo:thi]))

    s = num_shards
    ct = max(len(r["tslack"]) for r in rows)
    lb = max(len(r["nob"]) for r in rows)
    nb = max(len(r["backbone"]) for r in rows)
    mm = max(1, max(len(r["hashes"]) for r in rows))
    st_text = np.full((s, nb), SENT_NODE, np.uint32)
    st_nob = np.zeros((s, lb), np.int32)
    st_bb = np.full((s, nb), -1, np.int32)
    st_hash = np.full((s, mm), _PAD_HASH, np.uint32)
    st_pos = np.full((s, mm), _PAD_POS, np.int32)
    st_bloom = np.zeros((s, ct, tbloom.shape[-1]), np.uint32)
    st_slack = np.zeros((s, ct), np.int32)
    tile_base = np.zeros(s, np.int32)
    nb_offset = np.zeros(s, np.int32)
    node_base = np.zeros(s, np.int32)
    for i, r in enumerate(rows):
        st_text[i, : len(r["text"])] = r["text"]
        st_nob[i, : len(r["nob"])] = r["nob"]
        st_bb[i, : len(r["backbone"])] = r["backbone"]
        st_hash[i, : len(r["hashes"])] = r["hashes"]
        st_pos[i, : len(r["positions"])] = r["positions"]
        st_bloom[i, : len(r["tbloom"])] = r["tbloom"]
        st_slack[i, : len(r["tslack"])] = r["tslack"]
        tile_base[i] = r["tile_base"]
        nb_offset[i] = r["nb_offset"]
        node_base[i] = r["node_base"]
    arrays = GraphShardArrays(
        gtext=jnp.asarray(st_text), tile_base=jnp.asarray(tile_base),
        node_of_backbone=jnp.asarray(st_nob),
        nb_offset=jnp.asarray(nb_offset), backbone=jnp.asarray(st_bb),
        node_base=jnp.asarray(node_base), hashes=jnp.asarray(st_hash),
        positions=jnp.asarray(st_pos), tile_bloom=jnp.asarray(st_bloom),
        tile_slack=jnp.asarray(st_slack))
    return ShardedGraphIndex(
        arrays=arrays, layout=layout, ref=np.asarray(gidx.ref, np.int8),
        tile_len=gidx.tile_len, tile_stride=gidx.tile_stride,
        n_tiles=n_tiles,
        n_nodes=n_nodes, minimizer_w=gidx.minimizer_w,
        minimizer_k=gidx.minimizer_k, window=gidx.window, margin=gidx.margin)


class EpochedShardedGraphIndex:
    """Epoch-vector-stamped handle around a ``ShardedGraphIndex``.

    Mirrors `partition.EpochedShardedIndex`: ``refresh()`` rebuilds the
    graph from a new reference/variant set (all epochs bump);
    ``refresh_shard(i)`` re-slices shard ``i`` from the retained host
    ``GraphIndex`` (failover re-materialization, epoch ``i`` bumps).
    ``current()`` returns the hashable ``(layout_key, epoch vector)``
    token the serve cache keys on.
    """

    def __init__(self, sharded: ShardedGraphIndex, source: GraphIndex, *,
                 variants: Sequence[Variant] = (),
                 epochs: Sequence[int] | None = None):
        self._lock = threading.Lock()
        self._index = sharded
        self._source = source
        self._variants = tuple(variants)
        self.epochs = list(epochs) if epochs is not None \
            else [0] * sharded.num_shards
        if len(self.epochs) != sharded.num_shards:
            raise ValueError(
                f"epoch vector has {len(self.epochs)} entries for "
                f"{sharded.num_shards} shards")
        self._build_kw = dict(
            w=sharded.minimizer_w, k=sharded.minimizer_k,
            tile_stride=sharded.tile_stride, window=sharded.window,
            margin=sharded.margin)
        self._halo = sharded.layout.halo

    @property
    def index(self) -> ShardedGraphIndex:
        """The current ``ShardedGraphIndex`` (unsynchronized peek)."""
        return self._index

    def epoch_token(self) -> tuple:
        """Hashable (layout, epoch-vector) cache-key component."""
        with self._lock:
            return (self._index.layout_key, tuple(self.epochs))

    def current(self) -> tuple[ShardedGraphIndex, tuple]:
        """Consistent (index, epoch token) pair for one mapping batch."""
        with self._lock:
            return self._index, (self._index.layout_key, tuple(self.epochs))

    def refresh(self, ref: np.ndarray,
                variants: Sequence[Variant] | None = None,
                **build_kw) -> tuple:
        """Rebuild graph + shards from a new reference; bumps all epochs."""
        kw = {**self._build_kw, **build_kw}
        vs = self._variants if variants is None else tuple(variants)
        source = build_graph_index(ref, vs, **kw)
        new = shard_graph_index(source, self._index.num_shards,
                                halo=self._halo)
        with self._lock:
            self._index = new
            self._source = source
            self._variants = vs
            self._build_kw = kw
            self.epochs = [e + 1 for e in self.epochs]
            return (new.layout_key, tuple(self.epochs))

    def refresh_shard(self, i: int) -> tuple:
        """Re-slice shard ``i`` from the retained host graph index."""
        if not 0 <= i < self._index.num_shards:
            raise IndexError(f"shard {i} out of range "
                             f"(num_shards={self._index.num_shards})")
        fresh = shard_graph_index(self._source, self._index.num_shards,
                                  halo=self._halo)
        a, f = self._index.arrays, fresh.arrays
        with self._lock:
            self._index = ShardedGraphIndex(
                arrays=GraphShardArrays(*[
                    cur.at[i].set(new[i]) for cur, new in zip(a, f)]),
                layout=self._index.layout, ref=self._index.ref,
                tile_len=self._index.tile_len,
                tile_stride=self._index.tile_stride,
                n_tiles=self._index.n_tiles, n_nodes=self._index.n_nodes,
                minimizer_w=self._index.minimizer_w,
                minimizer_k=self._index.minimizer_k,
                window=self._index.window, margin=self._index.margin)
            self.epochs[i] += 1
            return (self._index.layout_key, tuple(self.epochs))


def from_epoched_graph(egi: EpochedGraphIndex | GraphIndex, num_shards: int,
                       *, halo: int = DEFAULT_HALO
                       ) -> EpochedShardedGraphIndex:
    """Shard an existing (epoched) graph index, reusing its built arrays."""
    if isinstance(egi, EpochedGraphIndex):
        gidx = egi.index
        variants = egi._variants
    else:
        gidx = egi
        variants = ()
    return EpochedShardedGraphIndex(
        shard_graph_index(gidx, num_shards, halo=halo), gidx,
        variants=variants)
