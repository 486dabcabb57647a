"""Plain reference for the read-mapping checks: data, graph, DP, CIGAR.

Imports nothing of the program under test and takes nothing it made.
Everything here is a straightforward restatement of the service's
semantics:

* the deployment's data from a seed: a uniform random reference and a
  variant list (spread, non-overlapping SNP/insertion/deletion sites);
* the variation graph those variants spell, linearized with one base per
  node, alt nodes placed right after their backbone position, and edges
  stored as hop bits (bit ``h`` of ``succ[i]`` set when node ``i+h+1``
  follows node ``i``);
* the anchored semi-global edit distance of a read against a window of
  that graph (a linear reference is the chain graph): the alignment
  starts at window node 0, consumes the whole read, and may stop
  anywhere;
* the check that a packed CIGAR (M=0, X=1, I=2, D=3) spells the read
  against the nodes it walks, with as many edits as reported.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

SENTINEL = 4  # padding base; equals no read base
HOP_LIMIT = 16  # longest edge a node's hop bits can hold
OP_M, OP_X, OP_I, OP_D = 0, 1, 2, 3
KINDS = ("snp", "ins", "del")


def random_reference(length: int, rng: np.random.Generator) -> np.ndarray:
    """Uniform random ACGT bases (ids 0..3), no repeats beyond chance."""
    return rng.integers(0, 4, size=length, dtype=np.int8)


class Variants(NamedTuple):
    pos: np.ndarray  # [V] int64 sorted backbone positions, distinct
    kind: np.ndarray  # [V] int8 index into KINDS
    alt: np.ndarray  # [V, A] int8 alt bases (snp, ins); unused for del
    alt_len: np.ndarray  # [V] int32 alt length (0 for del)
    span: np.ndarray  # [V] int32 deleted backbone bases (0 unless del)


def random_variants(ref: np.ndarray, *, every_bp: int, mix: dict,
                    ins_len: int, del_span: int, site_pitch: int,
                    rng: np.random.Generator) -> Variants:
    """One variant per ``every_bp`` backbone bases on a ``site_pitch`` grid.

    ``mix`` gives the kinds' weights (e.g. snp 2, ins 1, del 1); a SNP
    carries the next base ``(ref + 1) % 4``, an insertion ``ins_len``
    random bases after its site, a deletion drops ``del_span`` bases
    after its site.  Sites are distinct grid points, so variants never
    overlap when ``site_pitch`` exceeds ``del_span + 1``.
    """
    n_ref = len(ref)
    n_var = n_ref // every_bp
    total = sum(mix.values())
    counts = {k: n_var * mix.get(k, 0) // total for k in KINDS}
    sites = np.arange(4, n_ref - 8, site_pitch)
    n = min(sum(counts.values()), len(sites))
    pos = np.sort(rng.choice(sites, size=n, replace=False)).astype(np.int64)
    kind = np.concatenate([np.full(counts[k], i, np.int8)
                           for i, k in enumerate(KINDS)])[:n]
    rng.shuffle(kind)
    alt = np.zeros((n, max(ins_len, 1)), np.int8)
    alt_len = np.zeros(n, np.int32)
    span = np.zeros(n, np.int32)
    snp = kind == 0
    ins = kind == 1
    alt[snp, 0] = (ref[pos[snp]] + 1) % 4
    alt_len[snp] = 1
    alt[ins, :ins_len] = rng.integers(0, 4, size=(int(ins.sum()), ins_len),
                                      dtype=np.int8)
    alt_len[ins] = ins_len
    span[kind == 2] = del_span
    return Variants(pos, kind, alt, alt_len, span)


class Graph(NamedTuple):
    bases: np.ndarray  # [N] int8
    succ: np.ndarray  # [N] uint32 hop bits
    backbone: np.ndarray  # [N] int64 backbone coordinate (-1 on alt nodes)


def build_graph(ref: np.ndarray, v: Variants) -> Graph:
    """Linearize ``ref`` plus ``v`` into the one-base-per-node graph.

    Node order: backbone base ``p``, then the alt nodes of the variant
    at ``p``.  Edges: the backbone chain; an insertion's branch from
    base ``p`` back to ``p + 1``; a SNP branch from every predecessor of
    base ``p`` to base ``p + 1``; a deletion's jump from ``p`` to
    ``p + span + 1``.
    """
    n_ref = len(ref)
    if len(np.unique(v.pos)) != len(v.pos):
        raise ValueError("one variant per backbone position")
    extra = np.zeros(n_ref, np.int64)
    extra[v.pos] = v.alt_len
    nb = np.arange(n_ref, dtype=np.int64) + np.concatenate(
        [[0], np.cumsum(extra)[:-1]])  # node id of each backbone base
    n = n_ref + int(extra.sum())
    bases = np.empty(n, np.int8)
    backbone = np.full(n, -1, np.int64)
    bases[nb] = ref
    backbone[nb] = np.arange(n_ref)
    src = [nb[:-1]]
    dst = [nb[1:]]
    head = np.full(n, -1, np.int64)  # SNP branch head per backbone node
    for a in range(int(v.alt_len.max(initial=0))):
        has = v.alt_len > a
        ids = nb[v.pos[has]] + 1 + a
        bases[ids] = v.alt[has, a]
        if a:  # chain inside a branch
            src.append(ids - 1)
            dst.append(ids)
    branch = v.alt_len > 0
    last = nb[v.pos[branch]] + v.alt_len[branch]
    tail_ok = v.pos[branch] + 1 < n_ref
    src.append(last[tail_ok])  # a branch rejoins the next backbone base
    dst.append(nb[v.pos[branch][tail_ok] + 1])
    ins = v.kind == 1
    src.append(nb[v.pos[ins]])
    dst.append(nb[v.pos[ins]] + 1)
    dele = v.kind == 2
    land = v.pos[dele] + v.span[dele] + 1
    if np.any(land >= n_ref):
        raise ValueError("a deletion lands past the reference end")
    src.append(nb[v.pos[dele]])
    dst.append(nb[land])
    snp = v.kind == 0
    head[nb[v.pos[snp]]] = nb[v.pos[snp]] + 1
    s = np.concatenate(src)
    d = np.concatenate(dst)
    into = head[d] >= 0  # a SNP branch shares its backbone twin's preds
    s = np.concatenate([s, s[into]])
    d = np.concatenate([d, head[d[into]]])
    hop = d - s - 1
    if hop.min(initial=0) < 0 or hop.max(initial=0) >= HOP_LIMIT:
        raise ValueError("edge hop outside [1, HOP_LIMIT]")
    succ = np.zeros(n, np.uint32)
    for h in np.unique(hop):
        m = hop == h
        succ[s[m]] |= np.uint32(1 << int(h))
    return Graph(bases, succ, backbone)


def windows(bases: np.ndarray, succ: np.ndarray | None, starts: np.ndarray,
            lengths: np.ndarray):
    """``[R, T]`` node bases and in-window hop bits from each start.

    ``succ=None`` reads ``bases`` as a linear reference: the chain graph
    in which each base's one successor is the next.
    """
    t = int(lengths.max(initial=1))
    idx = starts[:, None] + np.arange(t)[None, :]
    inside = (np.arange(t)[None, :] < lengths[:, None]) & (idx < len(bases))
    idx = np.where(inside, idx, 0)
    b = np.where(inside, bases[idx], SENTINEL).astype(np.int8)
    s = np.where(inside, np.uint32(1) if succ is None else succ[idx],
                 0).astype(np.uint32)
    # drop hops that leave the window
    room = np.clip(lengths[:, None] - 1 - np.arange(t)[None, :], 0, 32)
    s &= np.where(room >= 32, np.uint32(0xFFFFFFFF),
                  (np.uint32(1) << room.astype(np.uint32)) - np.uint32(1))
    return b, s


def anchored_distance(reads: list[np.ndarray], bases: np.ndarray,
                      succ: np.ndarray) -> np.ndarray:
    """Anchored semi-global read-to-graph edit distance, one per row.

    Row ``r`` aligns ``reads[r]`` to the window ``bases[r]``/``succ[r]``:
    the first node consumed is window node 0 (skipping it costs a
    deletion), every read base is consumed, trailing nodes are free.
    ``A[i, j]`` is the least cost with ``i`` read bases consumed and
    node ``j`` consumed last.
    """
    r, t = bases.shape
    m = np.array([len(x) for x in reads])
    mmax = int(m.max(initial=0))
    pat = np.full((r, max(mmax, 1)), -1, np.int16)
    for k, x in enumerate(reads):
        pat[k, :len(x)] = x
    hops = [h for h in range(HOP_LIMIT) if np.any((succ >> h) & 1)]
    pred = {}  # pred[h][:, j]: node j - h - 1 has an edge to node j
    for h in hops:
        p = np.zeros((r, t), bool)
        p[:, h + 1:] = ((succ[:, :t - h - 1] >> np.uint32(h)) & 1) == 1
        pred[h] = p
    inf = np.int32(1 << 28)

    def shifted(a, h):
        out = np.full_like(a, inf)
        out[:, h + 1:] = a[:, :t - h - 1]
        return out

    def close(a):  # deletions: walk edges without consuming read bases
        while True:
            b = a
            for h in hops:
                b = np.where(pred[h], np.minimum(b, shifted(b, h) + 1), b)
            if np.array_equal(a, b):
                return a
            a = b

    out = m.astype(np.int64).copy()  # all insertions consume no node
    row = np.full((r, t), inf, np.int32)
    row[:, 0] = 1  # empty read prefix, start node deleted
    row = close(row)
    for i in range(1, mmax + 1):
        cost = (pat[:, i - 1, None] != bases).astype(np.int32)
        cur = row + 1  # insertion: read base i against node j again
        cur[:, 0] = np.minimum(cur[:, 0],
                               np.minimum(i + 1, i - 1 + cost[:, 0]))
        for h in hops:
            cur = np.where(pred[h],
                           np.minimum(cur, shifted(row, h) + cost), cur)
        row = close(np.minimum(cur, inf))
        done = m == i
        if done.any():
            out[done] = np.minimum(out[done], row[done].min(axis=1))
    return out


def cigar_error(ops: np.ndarray, read: np.ndarray, text: np.ndarray,
                distance: int) -> str | None:
    """None when ``ops`` spells ``read`` against ``text`` at ``distance``."""
    pi = ti = edits = 0
    for s, op in enumerate(ops.tolist()):
        if op in (OP_M, OP_X):
            if pi >= len(read) or ti >= len(text):
                return f"op {s} runs past the read or text"
            if (read[pi] == text[ti]) != (op == OP_M):
                return f"op {s} is {'MX'[op]} on read {read[pi]} text {text[ti]}"
            edits += op == OP_X
            pi += 1
            ti += 1
        elif op == OP_I:
            if pi >= len(read):
                return f"op {s} inserts past the read"
            pi += 1
            edits += 1
        elif op == OP_D:
            if ti >= len(text):
                return f"op {s} deletes past the text"
            ti += 1
            edits += 1
        else:
            return f"op {s} has the unknown code {op}"
    if pi != len(read):
        return f"{pi} of {len(read)} read bases consumed"
    if edits != distance:
        return f"{edits} edits spelled, {distance} reported"
    return None


def path_error(g: Graph, ops: np.ndarray, path: np.ndarray, read: np.ndarray,
               distance: int, position: int) -> str | None:
    """None when a graph answer walks edges and spells ``read``."""
    consumes = np.isin(ops, (OP_M, OP_X, OP_D))
    if not np.array_equal(path >= 0, consumes):
        return "path entries do not match the node-consuming ops"
    nodes = path[consumes].astype(np.int64)
    if len(nodes) == 0:
        return "the path consumes no node"
    if nodes.min() < 0 or nodes.max() >= len(g.bases):
        return "path node outside the graph"
    hop = nodes[1:] - nodes[:-1] - 1
    ok = (hop >= 0) & (hop < HOP_LIMIT)
    ok[ok] = ((g.succ[nodes[:-1][ok]] >> hop[ok].astype(np.uint32)) & 1) == 1
    if not ok.all():
        k = int(np.argmin(ok))
        return f"path steps {nodes[k]}->{nodes[k + 1]} along no edge"
    bb = g.backbone[nodes]
    first = bb[bb >= 0]
    want = int(first[0]) if len(first) else -1
    if want != position:
        return f"position {position}, first backbone node of the path {want}"
    return cigar_error(ops, read, g.bases[nodes], distance)
