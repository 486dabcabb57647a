"""Genome graphs for SeGraM (paper §2.5, §6.5).

A graph is a topologically-ordered DAG with one base per node (the paper's
nodes hold short sequences; one-base nodes are the same graph after
splitting, and make hopBits uniform).  Successor edges within a bounded
hop window are encoded as per-node **hopBits** (paper Figure 6-9): bit
``h`` of ``succ_bits[i]`` set ⇔ node ``i + h + 1`` is a successor of ``i``.
The linearization keeps variant branches adjacent to their backbone
position so real variation graphs have small hop distances; edges beyond
``HOP_LIMIT`` would need graph re-chunking (the paper picks the hop limit
so this does not occur; construction raises so the caller can re-chunk).

Construction is a few numpy passes over positions and variants, not a
loop: node ids come from a cumulative sum of each position's node count,
and hopBits from one shifted entry mask per edge source (a position's
entries are its backbone node and its SNP branch heads).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

HOP_LIMIT = 16


class Variant(NamedTuple):
    """pos: 0-based backbone position; kind: 'snp' | 'ins' | 'del'.

    snp: ``alt`` (len ≥ 1) replaces the ref base at pos (len > 1 spells a
    branch of chained nodes, e.g. an MNP allele).
    ins: ``alt`` inserted *after* backbone position pos.
    del: ``span`` backbone bases deleted starting at pos.
    """

    pos: int
    kind: str
    alt: tuple = ()
    span: int = 1


@dataclass
class GenomeGraph:
    bases: np.ndarray  # [N] int8, topological order
    succ_bits: np.ndarray  # [N] uint32 hopBits (successors)
    backbone: np.ndarray  # [N] int32 backbone coordinate of each node (-1 for alt)
    node_of_backbone: np.ndarray  # [L] int32 node id of each backbone position

    @property
    def n_nodes(self) -> int:
        return int(self.bases.shape[0])


_KIND = {"snp": 0, "ins": 1, "del": 2}


def _columns(variants):
    """A ``Variant`` list as columns ``(pos, kind, alt_len, alt, span)``:
    kind coded by ``_KIND`` (-1 unknown), every allele flattened into
    ``alt`` in list order."""
    if not len(variants):
        z = np.zeros(0, np.int64)
        return z, z, z, np.zeros(0, np.int8), z
    pos, kind, alt, span = zip(*variants)
    n = len(pos)
    alt_len = np.fromiter(map(len, alt), np.int64, n)
    flat = np.fromiter(itertools.chain.from_iterable(alt), np.int8,
                       int(alt_len.sum()))
    names = np.asarray(kind, dtype=object)
    code = np.full(n, -1, np.int64)
    for name, c in _KIND.items():
        code[names == name] = c
    return (np.fromiter(pos, np.int64, n), code, alt_len, flat,
            np.fromiter(span, np.int64, n))


def _first_error(variants, idx, pos, kind, alt_len, span, n_ref) -> None:
    """Raise for the malformed variant a walk over backbone positions
    meets first: the lowest position, the earlier in the list on a tie."""
    tgt = pos + span + 1
    bad = ((kind < 0) | ((kind == 0) & (alt_len == 0))
           | ((kind == 2) & (tgt >= n_ref)))
    if not bad.any():
        return
    j = np.flatnonzero(bad)[np.argmin(pos[bad])]  # argmin: first on a tie
    v = variants[int(idx[j])]
    if kind[j] < 0:
        raise ValueError(v.kind)
    if kind[j] == 0:
        raise ValueError(f"snp at {v.pos} needs a non-empty alt")
    raise ValueError(
        f"del at {v.pos} (span {v.span}) lands at backbone {int(tgt[j])}, "
        f"past the reference end {n_ref}; trim the variant or extend the "
        f"reference")


def _runs(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """``concat([arange(s, s + n) for s, n in zip(starts, lens)])``."""
    first = np.cumsum(lens) - lens
    return np.repeat(starts - first, lens) + np.arange(int(lens.sum()))


def build_graph(ref: np.ndarray, variants=()) -> GenomeGraph:
    """Build a variation graph from a linear reference + variant list.

    Node order: each backbone position's base, then the alt nodes of the
    variants at that position (``snp`` branches and ``ins`` chains, in
    list order).  A position's *entries* are its backbone node and the
    first node of each of its ``snp`` branches; each takes an edge from
    the backbone node before it, from the last node of every branch or
    insertion there, and from every deletion landing on the position
    (``pos + span + 1``).  An insertion's first node follows its own
    backbone node.  Variants off the reference are ignored, as is a
    deletion landing at or before its own position.

    Raises ``ValueError`` for malformed variants: an empty ``snp`` alt, a
    deletion whose landing position falls past the reference end (it
    would silently vanish otherwise), or any edge whose hop distance
    exceeds ``HOP_LIMIT``.
    """
    L = len(ref)
    pos, kind, alt_len, flat, span = _columns(variants)
    alt_at = np.cumsum(alt_len) - alt_len  # each allele's start in ``flat``
    idx = np.flatnonzero((pos >= 0) & (pos < L))
    _first_error(variants, idx, pos[idx], kind[idx], alt_len[idx],
                 span[idx], L)
    idx = idx[np.argsort(pos[idx], kind="stable")]  # list order on a tie
    pos, kind, alt_len, alt_at, span = (
        a[idx] for a in (pos, kind, alt_len, alt_at, span))

    # node ids: a cumulative sum of each position's node count
    n_alt = np.where(kind < 2, alt_len, 0)
    counts = np.ones(L, np.uint32)
    np.add.at(counts, pos, n_alt.astype(np.uint32))
    nob = np.cumsum(counts, dtype=np.int64)
    n = int(nob[-1]) if L else 0
    if n >= 2 ** 31:
        raise ValueError(f"{n} nodes overflow int32 node ids")
    nob -= counts
    nob = nob.astype(np.int32)  # node of each backbone position
    before = np.cumsum(n_alt) - n_alt  # alt nodes of earlier variants
    first = nob[pos] + 1 + before - before[np.searchsorted(pos, pos)]
    bases = np.empty(n, np.int8)
    bases[nob] = ref
    backbone = np.full(n, -1, np.int32)
    backbone[nob] = np.arange(L, dtype=np.int32)
    bases[_runs(first, n_alt)] = flat[_runs(alt_at, n_alt)]

    # a position's entries as bits above its backbone node: bit 0, and the
    # offset of each snp head (``heads``, few: kept sparse in zeroed
    # arrays); ``top`` is the furthest entry's offset
    has = n_alt > 0
    head = (kind == 0) & has
    off = (first - nob[pos])[head]
    extra = np.zeros(L, np.uint32)
    near = off < HOP_LIMIT
    np.bitwise_or.at(extra, pos[head][near],
                     np.uint32(1) << off[near].astype(np.uint32))
    top = np.zeros(L, np.int32)
    np.maximum.at(top, pos[head], off.astype(np.int32))
    heads = np.unique(pos[head])
    heads = heads[heads > 0]

    # sparse source groups: (sources, hop of the edge to the backbone
    # entry, target position); the backbone chain, which holds nearly every
    # edge, is handled whole below
    tail = first + n_alt - 1
    dels = (kind == 2) & (span >= 0)
    groups = []
    for s, p in ((tail[has], pos[has] + 1), (nob[pos[dels]],
                                             (pos + span + 1)[dels])):
        s, p = s[p < L], p[p < L]  # tails at the last position lead nowhere
        groups.append((s, nob[p] - s - 1, p))
    ins = (kind == 1) & has
    ih = nob[pos[ins]]
    groups.append((ih, first[ins] - ih - 1, None))
    chain = counts[:-1] - np.uint32(1)  # hop from each backbone node

    def reach(s, hop, p):  # (hop, target) of each source's furthest edge
        far = hop.astype(np.int64) + (0 if p is None else top[p])
        return far, s + far + 1

    worst = max([int(chain.max(initial=0)),
                 int((chain[heads - 1] + top[heads]).max(initial=0))]
                + [int(reach(*g)[0].max()) for g in groups if len(g[0])])
    if worst >= HOP_LIMIT:  # the loop named the first such edge it made
        whole = (nob[:-1], chain, np.arange(1, L))
        far = min(int(t[h == worst].min())
                  for h, t in (reach(*g) for g in [whole] + groups)
                  if (h == worst).any())
        raise ValueError(
            f"edge {far - worst - 1}->{far} hop {worst + 1} exceeds "
            f"HOP_LIMIT={HOP_LIMIT}; re-chunk the graph")

    succ = np.zeros(n, np.uint32)
    bits = np.left_shift(np.uint32(1), chain)
    bits[heads - 1] |= extra[heads] << chain[heads - 1]
    succ[nob[:-1]] = bits  # distinct sources
    del bits
    for s, hop, p in groups:
        entry = np.uint32(1) if p is None else extra[p] | np.uint32(1)
        np.bitwise_or.at(succ, s, entry << hop.astype(np.uint32))
    succ[_runs(first, np.maximum(n_alt - 1, 0))] |= np.uint32(1)  # alleles
    return GenomeGraph(bases=bases, succ_bits=succ, backbone=backbone,
                       node_of_backbone=nob)


def linear_graph(ref: np.ndarray) -> GenomeGraph:
    """Degenerate graph (pure backbone) — BitAlign on it must equal linear Bitap."""
    return build_graph(ref, [])


def hop_boundary_mask(length: int, valid_len) -> jnp.ndarray:
    """The one boundary-masking rule for subgraph windows.

    Returns ``[length] uint32``: entry ``i`` keeps hop bit ``h`` iff the
    target node ``i + h + 1`` stays below ``valid_len`` (the window/graph
    end).  ``valid_len`` may be a traced scalar; every window extractor —
    host-side :func:`extract_subgraph`, device-side ``segram._window``,
    and the tile builder in `repro.graph.index` — applies this mask so
    out-of-window hops cannot disagree between paths.
    """
    room = jnp.clip(
        jnp.asarray(valid_len, jnp.int32) - 1 - jnp.arange(length), 0, 32)
    return jnp.where(
        room >= 32, jnp.uint32(0xFFFFFFFF),
        (jnp.uint32(1) << room.astype(jnp.uint32)) - 1)


def extract_subgraph(g: GenomeGraph, start_node: int, length: int):
    """Fixed-size window of the linearized graph for one candidate region.

    Returns (bases [length] int8 sentinel-padded, succ_bits [length] uint32
    masked at the boundary).
    """
    n = g.n_nodes
    s = max(0, min(start_node, n))
    e = min(n, s + length)
    bases = np.full(length, 4, np.int8)
    succ = np.zeros(length, np.uint32)
    bases[: e - s] = g.bases[s:e]
    succ[: e - s] = g.succ_bits[s:e]
    succ &= np.asarray(hop_boundary_mask(length, e - s))
    return bases, succ


def predecessors(g: GenomeGraph) -> list[list[int]]:
    """Adjacency (predecessor lists) for the numpy DP oracle."""
    preds: list[list[int]] = [[] for _ in range(g.n_nodes)]
    for i in range(g.n_nodes):
        bits = int(g.succ_bits[i])
        h = 0
        while bits:
            if bits & 1:
                preds[i + h + 1].append(i)
            bits >>= 1
            h += 1
    return preds
