"""Tiled graph-reference index: graphs far longer than one BitAlign window.

The whole linearized graph lives on device once, *plus* a tiled view:
overlapping fixed-size tiles at ``tile_stride`` node pitch, each packed
as graph text (`windowed.pack_graph_text`) with its hopBits cut at the
tile boundary by the one shared masking rule
(`core/segram/graph.hop_boundary_mask`).  A candidate backbone position
maps to a tile via ``node // tile_stride`` — no per-read dynamic slicing
of the full graph; the mapper's candidate windows are **one gather**
``tile_gtext[tile_ids]`` per batch, which is what turns per-read
per-candidate scans into a single ``[B, max_candidates]`` BitAlign-DC
launch per step.

Tile geometry: ``tile_len = tile_stride + margin + window``.  A
candidate's anchor is refined inside ``[0, tile_stride + margin)`` (the
first ``tile_stride`` nodes own the tile, ``margin`` absorbs seed
quantization + leading-variation drift), and ``window`` nodes of
alignment text always remain past any refined anchor.  Edges whose hop
would exceed ``HOP_LIMIT`` keep raising in `build_graph` — the tiling
re-chunks *windows*, not edges, so the invariant the BitAlign PE design
relies on (Figure 6-8's bounded hop queue) holds per tile by
construction.

``EpochedGraphIndex`` mirrors `core/minimizer_index.EpochedIndex`: the
serve engine keys its result cache and compiled executors on the epoch,
so hot-swapping a rebuilt graph atomically invalidates both.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitvector import SENTINEL
from repro.core.filter import QGRAM_Q, qgram_bloom
from repro.core.segram.graph import (GenomeGraph, Variant, build_graph,
                                     hop_boundary_mask)
from repro.core.segram.minimizer import build_index

from .windowed import pack_graph_text

DEFAULT_WINDOW = 256
DEFAULT_STRIDE = 64
DEFAULT_MARGIN = 64


class GraphArrays(NamedTuple):
    """Device half of the index (a jit-traceable pytree)."""

    bases: jnp.ndarray  # [N] int8 linearized graph
    succ_bits: jnp.ndarray  # [N] uint32 hopBits
    backbone: jnp.ndarray  # [N] int32 backbone coord (-1 for alt nodes)
    node_of_backbone: jnp.ndarray  # [L] int32
    tile_gtext: jnp.ndarray  # [C, tile_len] uint32 packed tiles
    tile_valid: jnp.ndarray  # [C] int32 valid node count per tile
    idx_hashes: jnp.ndarray  # [M] uint32 sorted backbone minimizers
    idx_positions: jnp.ndarray  # [M] int32
    tile_bloom: jnp.ndarray  # [C, BLOOM_WORDS] uint32 per-tile q-gram Bloom
    tile_slack: jnp.ndarray  # [C] int32 (q-1)·(hop>1 edges) screen slack


@dataclass
class GraphIndex:
    """Host handle: device arrays + the static geometry the mapper needs."""

    arrays: GraphArrays
    ref: np.ndarray  # host reference copy (GAF tlen, refresh)
    tile_len: int
    tile_stride: int
    minimizer_w: int
    minimizer_k: int
    window: int = DEFAULT_WINDOW  # recorded so refresh() reproduces geometry
    margin: int = DEFAULT_MARGIN

    @property
    def n_nodes(self) -> int:
        return int(self.arrays.bases.shape[0])

    @property
    def n_tiles(self) -> int:
        return int(self.arrays.tile_gtext.shape[0])

    @property
    def ref_len(self) -> int:
        return int(len(self.ref))


# tiles per device pass of the tile build: one tile row costs ~50 B per
# node of q-gram/gather intermediates, so large graphs build in chunks
TILE_CHUNK = 8192


@partial(jax.jit, static_argnames=("n_rows", "tile_len", "tile_stride"))
def _tile_rows(bases: jnp.ndarray, succ: jnp.ndarray, row0, *, n_rows: int,
               tile_len: int, tile_stride: int):
    """Tiles ``row0 .. row0 + n_rows`` (rows past the graph are empty)."""
    n = bases.shape[0]
    starts = (row0 + jnp.arange(n_rows)) * tile_stride
    idx = starts[:, None] + jnp.arange(tile_len)[None, :]
    inb = idx < n
    idxc = jnp.clip(idx, 0, n - 1)
    tb = jnp.where(inb, bases[idxc], SENTINEL).astype(jnp.int8)
    ts = jnp.where(inb, succ[idxc], jnp.uint32(0))
    valid = jnp.clip(n - starts, 0, tile_len).astype(jnp.int32)
    mask = jax.vmap(lambda v: hop_boundary_mask(tile_len, v))(valid)
    ts_m = ts & mask
    # tile pre-filter payload: a Bloom filter over the tile's q-grams and
    # the q-gram-lemma slack for alt paths — a matching path may spell up
    # to q-1 q-grams across each hop>1 edge (bits 1.. of the masked
    # hopBits) that are not substrings of the linearization
    bloom = jax.vmap(qgram_bloom)(tb, valid)
    in_valid = jnp.arange(tile_len)[None, :] < valid[:, None]
    hop_edges = jnp.where(in_valid, jax.lax.population_count(ts_m >> 1), 0)
    slack = ((QGRAM_Q - 1) *
             jnp.sum(hop_edges, axis=-1)).astype(jnp.int32)
    return pack_graph_text(tb, ts_m), valid, bloom, slack


def _build_tiles(bases: jnp.ndarray, succ: jnp.ndarray, *, tile_len: int,
                 tile_stride: int):
    n = bases.shape[0]
    c = max(1, -(-int(n) // tile_stride))
    rows = min(c, TILE_CHUNK)
    parts = [_tile_rows(bases, succ, r0, n_rows=rows, tile_len=tile_len,
                        tile_stride=tile_stride)
             for r0 in range(0, c, rows)]
    if len(parts) == 1:
        return parts[0]
    return tuple(jnp.concatenate(p)[:c] for p in zip(*parts))


def build_graph_index(
    ref: np.ndarray,
    variants: Sequence[Variant] = (),
    *,
    w: int = 10,
    k: int = 15,
    freq_frac: float = 0.0002,
    window: int = DEFAULT_WINDOW,
    tile_stride: int = DEFAULT_STRIDE,
    margin: int = DEFAULT_MARGIN,
    graph: GenomeGraph | None = None,
) -> GraphIndex:
    """Offline pre-processing (paper §6.5): graph + minimizers + tiles.

    ``window`` must cover the largest alignment text cap the mapper will
    slice (``p_cap + 2·cfg.w``); `repro.graph.mapper.map_batch` checks.
    """
    g = graph if graph is not None else build_graph(ref, list(variants))
    idx = build_index(ref, w=w, k=k, freq_frac=freq_frac)
    bases = jnp.asarray(g.bases)
    succ = jnp.asarray(g.succ_bits)
    tile_len = tile_stride + margin + window
    tiles, valid, bloom, slack = _build_tiles(bases, succ, tile_len=tile_len,
                                              tile_stride=tile_stride)
    arrays = GraphArrays(
        bases=bases,
        succ_bits=succ,
        backbone=jnp.asarray(g.backbone),
        node_of_backbone=jnp.asarray(g.node_of_backbone),
        tile_gtext=tiles,
        tile_valid=valid,
        idx_hashes=jnp.asarray(idx.hashes),
        idx_positions=jnp.asarray(idx.positions),
        tile_bloom=bloom,
        tile_slack=slack,
    )
    return GraphIndex(arrays=arrays, ref=np.asarray(ref, np.int8),
                      tile_len=tile_len, tile_stride=tile_stride,
                      minimizer_w=w, minimizer_k=k, window=window,
                      margin=margin)


class EpochedGraphIndex:
    """Epoch-stamped handle around a ``GraphIndex`` (serving hot swap).

    ``refresh()`` rebuilds from a new reference and/or variant list and
    bumps ``epoch``; the serve engine's result cache keys on the epoch so
    every result mapped against the old graph is atomically invalidated,
    and its compiled executors re-trace on the new tile shapes.
    """

    def __init__(self, index: GraphIndex, *, variants: Sequence[Variant] = (),
                 epoch: int = 0, **build_kw):
        self._lock = threading.Lock()
        self._index = index
        self._variants = tuple(variants)
        self.epoch = epoch
        kw = dict(w=index.minimizer_w, k=index.minimizer_k,
                  tile_stride=index.tile_stride, window=index.window,
                  margin=index.margin)
        kw.update(build_kw)  # explicit build kwargs win
        self._build_kw = kw

    @property
    def index(self) -> GraphIndex:
        return self._index

    def current(self) -> tuple[GraphIndex, int]:
        """Consistent (index, epoch) pair for one mapping batch."""
        with self._lock:
            return self._index, self.epoch

    def refresh(self, ref: np.ndarray,
                variants: Sequence[Variant] | None = None, **build_kw) -> int:
        """Rebuild from a new reference/variant set; returns the new epoch."""
        kw = {**self._build_kw, **build_kw}
        vs = self._variants if variants is None else tuple(variants)
        new = build_graph_index(ref, vs, **kw)
        with self._lock:
            self._index = new
            self._variants = vs
            self._build_kw = kw
            self.epoch += 1
            return self.epoch


def build_epoched_graph_index(ref: np.ndarray,
                              variants: Sequence[Variant] = (),
                              **build_kw) -> EpochedGraphIndex:
    """Build a graph index wrapped in an epoch-stamped serving handle."""
    return EpochedGraphIndex(build_graph_index(ref, variants, **build_kw),
                             variants=variants, **build_kw)


def save_graph_index(path: str | Path, gidx: GraphIndex) -> None:
    """Persist to npz (tiles are re-derived on load, not stored)."""
    a = gidx.arrays
    np.savez_compressed(
        path,
        bases=np.asarray(a.bases),
        succ_bits=np.asarray(a.succ_bits),
        backbone=np.asarray(a.backbone),
        node_of_backbone=np.asarray(a.node_of_backbone),
        idx_hashes=np.asarray(a.idx_hashes),
        idx_positions=np.asarray(a.idx_positions),
        ref=np.asarray(gidx.ref),
        meta=np.asarray([gidx.tile_len, gidx.tile_stride, gidx.minimizer_w,
                         gidx.minimizer_k, gidx.window, gidx.margin],
                        np.int64),
    )


def load_graph_index(path: str | Path) -> GraphIndex:
    with np.load(path) as z:
        tile_len, tile_stride, w, k, window, margin = (
            int(x) for x in z["meta"])
        bases = jnp.asarray(z["bases"])
        succ = jnp.asarray(z["succ_bits"])
        tiles, valid, bloom, slack = _build_tiles(
            bases, succ, tile_len=tile_len, tile_stride=tile_stride)
        arrays = GraphArrays(
            bases=bases,
            succ_bits=succ,
            backbone=jnp.asarray(z["backbone"]),
            node_of_backbone=jnp.asarray(z["node_of_backbone"]),
            tile_gtext=tiles,
            tile_valid=valid,
            idx_hashes=jnp.asarray(z["idx_hashes"]),
            idx_positions=jnp.asarray(z["idx_positions"]),
            tile_bloom=bloom,
            tile_slack=slack,
        )
        return GraphIndex(arrays=arrays, ref=z["ref"].astype(np.int8),
                          tile_len=tile_len, tile_stride=tile_stride,
                          minimizer_w=w, minimizer_k=k, window=window,
                          margin=margin)
