"""Graph-reference index: one packed graph text, windows gathered from it.

The linearized graph lives on the device once, as packed graph text
(`windowed.pack_graph_text`: one uint32 per node, base and hopBits).  A
candidate backbone position maps to a tile via ``node // tile_stride``;
its window is gathered from the text at ``tile * tile_stride`` and cut at
its own end by the one shared masking rule
(`core/segram/graph.hop_boundary_mask`), so a gathered window is bitwise
`extract_subgraph` at the same start and length (`gather_windows`).  The
mapper gathers one ``[rows, length]`` block per batch, at the length the
bucket rung needs, which keeps one ``[B, max_candidates]`` BitAlign-DC
launch per step.

Per tile the index keeps only the q-gram screen's payload, over the tile
``tile_len = tile_stride + margin + window`` nodes long: a Bloom filter
of its q-grams and the q-gram-lemma slack of its hop>1 edges.  A
candidate's anchor is refined inside ``[0, tile_stride + margin)`` (the
first ``tile_stride`` nodes own the tile, ``margin`` absorbs seed
quantization + leading-variation drift), and ``window`` nodes of
alignment text always remain past any refined anchor.  Edges whose hop
would exceed ``HOP_LIMIT`` keep raising in `build_graph` — windows are
cut from the graph, not edges, so the invariant the BitAlign PE design
relies on (Figure 6-8's bounded hop queue) holds per window by
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

from repro.core.filter import BLOOM_WORDS, QGRAM_Q, _bloom_probes
from repro.core.segram.graph import (HOP_LIMIT, GenomeGraph, Variant,
                                     build_graph, hop_boundary_mask)
from repro.core.segram.minimizer import build_index

from .windowed import SENT_NODE, pack_graph_text, unpack_graph_text

DEFAULT_WINDOW = 256
DEFAULT_STRIDE = 64
DEFAULT_MARGIN = 64


class GraphArrays(NamedTuple):
    """Device half of the index (a jit-traceable pytree)."""

    gtext: jnp.ndarray  # [N] uint32 packed graph text, each node once
    backbone: jnp.ndarray  # [N] int32 backbone coord (-1 for alt nodes)
    node_of_backbone: jnp.ndarray  # [L] int32
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
        return int(self.arrays.gtext.shape[0])

    @property
    def n_tiles(self) -> int:
        return int(self.arrays.tile_slack.shape[0])

    @property
    def ref_len(self) -> int:
        return int(len(self.ref))

    @property
    def geometry(self) -> tuple[int, int, int]:
        """``(tile_stride, margin, window)``: what a mapper bakes in."""
        return self.tile_stride, self.margin, self.window


def gather_windows(gtext: jnp.ndarray, starts: jnp.ndarray, length: int, *,
                   n_nodes: int, node_base=0) -> jnp.ndarray:
    """Packed windows ``[..., length]`` of the graph at global nodes
    ``starts``, bitwise `extract_subgraph` at the same start and length.

    ``gtext`` holds nodes ``node_base ..`` of an ``n_nodes``-node graph
    (all of it in the whole-graph view); nodes past the graph end read as
    sentinels, and hopBits leaving a window are cut at its end.
    """
    with jax.named_scope("graph_window_gather"):
        starts = jnp.asarray(starts, jnp.int32)
        idx = starts[..., None] + jnp.arange(length, dtype=jnp.int32)
        local = jnp.clip(idx - node_base, 0, gtext.shape[-1] - 1)
        win = jnp.where(idx < n_nodes, gtext[local], jnp.uint32(SENT_NODE))
        valid = jnp.clip(n_nodes - starts, 0, length)
        mask = hop_boundary_mask(length, valid[..., None])
        return win & ((mask << 8) | jnp.uint32(0xFF))


# tiles per device pass of the screen build: a pass reads one contiguous
# slice of the text and sets the Bloom bits of every block in it at once
TILE_CHUNK = 8192


def _window_or(x: jnp.ndarray, n: int, rows: int) -> jnp.ndarray:
    """``out[c] = x[c] | .. | x[c + n - 1]`` for ``c < rows``, by doubling."""
    out, off, run = jnp.zeros_like(x[:rows]), 0, x
    for bit in range(n.bit_length()):
        step = 1 << bit
        if n & step:
            out = out | run[off:off + rows]
            off += step
        if step * 2 <= n:
            run = run[:-step] | run[step:]
    return out


def _block_blooms(x: jnp.ndarray, n_blocks: int, size: int, part: int):
    """Bloom words ``[n_blocks, BLOOM_WORDS]`` of the q-grams starting at
    offsets ``[0, part)`` (``head``) and ``[part, size)`` (``rest``) of each
    ``size``-node block of the packed text ``x``.

    A block's 2·``size`` probes set their bits through one one-hot matmul
    (probe → word times probe → bit, counted), not a scatter per q-gram.
    """
    q, m = QGRAM_Q, n_blocks * size
    base = (x & jnp.uint32(0xFF)).astype(jnp.int32)
    ok = jnp.ones((m,), bool)
    code = jnp.zeros((m,), jnp.uint32)
    for j in range(q):  # q-gram codes by shifted slices, no gather
        b = base[j:j + m]
        ok = ok & (b < 4)
        code = code | ((b & 3).astype(jnp.uint32) << (2 * (q - 1 - j)))
    probes = jnp.stack(_bloom_probes(code), -1).reshape(n_blocks, 2 * size)
    ok = jnp.repeat(ok.reshape(n_blocks, size), 2, axis=-1)
    head = jnp.repeat(jnp.arange(size) < part, 2)
    word = ((probes >> 5)[..., None]
            == jnp.arange(BLOOM_WORDS, dtype=jnp.uint32))
    bit = (probes & 31)[..., None] == jnp.arange(32, dtype=jnp.uint32)
    bits = jnp.concatenate([bit & (ok & head)[..., None],
                            bit & (ok & ~head)[..., None]], -1)
    count = jnp.einsum("rpw,rpk->rwk", word.astype(jnp.bfloat16),
                       bits.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    weight = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    packed = jnp.sum(jnp.where(count > 0, jnp.tile(weight, 2), 0).reshape(
        n_blocks, BLOOM_WORDS, 2, 32), axis=-1, dtype=jnp.uint32)
    return packed[..., 0], packed[..., 1]


def _screen_rows(text: jnp.ndarray, row0, *, rows: int, tile_len: int,
                 tile_stride: int):
    """Bloom and slack of tiles ``row0 .. row0 + rows`` from one slice of
    ``text`` (the graph text with sentinels past its end).

    A tile's Bloom holds the q-grams starting in its first ``tile_len -
    q + 1`` nodes: ``full`` whole blocks of ``tile_stride`` nodes and a
    ``part``-node prefix of the next, so it is the OR of per-block Blooms
    (each q-gram hashed once, not once per overlapping tile).
    """
    q, s, h = QGRAM_Q, tile_stride, HOP_LIMIT
    full, part = divmod(tile_len - q + 1, s)
    n_blocks = rows + full + 1
    x = jax.lax.dynamic_slice(text, (row0 * s,), (n_blocks * s + q - 1,))
    head, rest = _block_blooms(x, n_blocks, s, part)
    bloom = _window_or(head | rest, full, rows) | head[full:full + rows]
    # slack: the q-gram-lemma allowance for alt paths — a matching path may
    # spell up to q-1 q-grams across each hop>1 edge (bits 1.. of the
    # hopBits) that are not substrings of the linearization.  Edges of a
    # tile's last ``h`` nodes are cut at its end; no edge leaves the graph
    hops = lambda t: jax.lax.population_count(  # noqa: E731
        (t >> 9) & jnp.uint32((1 << (h - 1)) - 1)).astype(jnp.int32)
    cs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(hops(x[:n_blocks * s]))])
    body = cs[tile_len - h::s][:rows] - cs[::s][:rows]
    last = x[tile_len - h:tile_len - h + rows * s].reshape(rows, s)[:, :h]
    cut = (hop_boundary_mask(h, h) << 8) | jnp.uint32(0xFF)
    slack = (q - 1) * (body + jnp.sum(hops(last & cut), axis=-1))
    return bloom, slack.astype(jnp.int32)


@partial(jax.jit, donate_argnums=(1, 2),
         static_argnames=("rows", "tile_len", "tile_stride"))
def _put_screen(text, bloom, slack, row0, *, rows: int, tile_len: int,
                tile_stride: int):
    b, s = _screen_rows(text, row0, rows=rows, tile_len=tile_len,
                        tile_stride=tile_stride)
    return (jax.lax.dynamic_update_slice(bloom, b, (row0, 0)),
            jax.lax.dynamic_update_slice(slack, s, (row0,)))


def _build_screen(gtext: jnp.ndarray, *, tile_len: int, tile_stride: int):
    """``(tile_bloom [C, BLOOM_WORDS], tile_slack [C])`` of every tile,
    ``TILE_CHUNK`` tiles a pass; the last pass ends at the last tile."""
    if tile_stride < HOP_LIMIT or tile_len < HOP_LIMIT + QGRAM_Q:
        raise ValueError(f"tile_stride {tile_stride} / tile_len {tile_len} "
                         f"too short for the screen")
    n = int(gtext.shape[0])
    c = max(1, -(-n // tile_stride))
    rows = min(c, TILE_CHUNK)
    pad = (tile_len // tile_stride + 3) * tile_stride + QGRAM_Q
    text = jnp.concatenate([gtext, jnp.full((pad,), SENT_NODE, jnp.uint32)])
    bloom = jnp.zeros((c, BLOOM_WORDS), jnp.uint32)
    slack = jnp.zeros((c,), jnp.int32)
    for r0 in range(0, c, rows):
        bloom, slack = _put_screen(text, bloom, slack, min(r0, c - rows),
                                   rows=rows, tile_len=tile_len,
                                   tile_stride=tile_stride)
    return bloom, slack


@jax.jit
def _pack(bases: jnp.ndarray, succ: jnp.ndarray) -> jnp.ndarray:
    return pack_graph_text(bases, succ)


def _device_arrays(bases, succ, backbone, node_of_backbone, hashes,
                   positions, *, tile_len: int, tile_stride: int
                   ) -> GraphArrays:
    gtext = _pack(jnp.asarray(bases), jnp.asarray(succ))
    bloom, slack = _build_screen(gtext, tile_len=tile_len,
                                 tile_stride=tile_stride)
    return GraphArrays(
        gtext=gtext,
        backbone=jnp.asarray(backbone),
        node_of_backbone=jnp.asarray(node_of_backbone),
        idx_hashes=jnp.asarray(hashes),
        idx_positions=jnp.asarray(positions),
        tile_bloom=bloom,
        tile_slack=slack,
    )


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
    """Offline pre-processing (paper §6.5): graph + minimizers + screen.

    ``window`` must cover the largest alignment text cap the mapper will
    slice (``p_cap + 2·cfg.w``); `repro.graph.mapper.map_batch` checks.
    """
    g = graph if graph is not None else build_graph(ref, variants)
    idx = build_index(ref, w=w, k=k, freq_frac=freq_frac)
    tile_len = tile_stride + margin + window
    arrays = _device_arrays(g.bases, g.succ_bits, g.backbone,
                            g.node_of_backbone, idx.hashes, idx.positions,
                            tile_len=tile_len, tile_stride=tile_stride)
    return GraphIndex(arrays=arrays, ref=np.asarray(ref, np.int8),
                      tile_len=tile_len, tile_stride=tile_stride,
                      minimizer_w=w, minimizer_k=k, window=window,
                      margin=margin)


class EpochedGraphIndex:
    """Epoch-stamped handle around a ``GraphIndex`` (serving hot swap).

    ``refresh()`` rebuilds from a new reference and/or variant list and
    bumps ``epoch``; the serve engine's result cache keys on the epoch so
    every result mapped against the old graph is atomically invalidated,
    and its compiled executors re-trace on the new graph's shapes.
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
    """Persist to npz (the screen is re-derived on load, not stored)."""
    a = gidx.arrays
    bases, succ = unpack_graph_text(np.asarray(a.gtext))
    np.savez_compressed(
        path,
        bases=np.asarray(bases),
        succ_bits=np.asarray(succ),
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
        arrays = _device_arrays(
            z["bases"], z["succ_bits"], z["backbone"], z["node_of_backbone"],
            z["idx_hashes"], z["idx_positions"], tile_len=tile_len,
            tile_stride=tile_stride)
        return GraphIndex(arrays=arrays, ref=z["ref"].astype(np.int8),
                          tile_len=tile_len, tile_stride=tile_stride,
                          minimizer_w=w, minimizer_k=k, window=window,
                          margin=margin)
