"""Plain reference for the read-mapping checks: data, graph, DP, CIGAR.

Imports nothing of the program under test and takes nothing it made.
Everything here is a straightforward restatement of the service's
semantics:

* the deployment's data from a seed: a uniform random reference and a
  variant list (spread, non-overlapping SNP/insertion/deletion sites);
* the variation graph those variants spell, linearized with one base per
  node, alt nodes placed right after their backbone position, and edges
  stored as hop bits (bit ``h`` of ``succ[i]`` set when node ``i+h+1``
  follows node ``i``); a check builds only the nodes around an answer,
  under the whole graph's node ids;
* a sample's haplotype: the backbone with the alt allele taken at some
  variant sites, spelled as one sequence that reads are drawn from;
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


def length_table(spec) -> dict[int, float]:
    """An indel length spec as ``{length: weight}``: an int is one length.
    Lengths lie in 1..15, the longest edge a node's hop bits hold."""
    table = ({int(spec): 1.0} if isinstance(spec, int)
             else {int(k): float(w) for k, w in spec.items()})
    if min(table) < 1 or max(table) >= HOP_LIMIT:
        raise ValueError(f"indel lengths must lie in 1..{HOP_LIMIT - 1}")
    return table


def _lengths(spec, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` indel lengths: an int is every length, drawing nothing; a
    table gives each length its share of ``n`` exactly (largest
    remainders), in an order shuffled by ``rng``."""
    if isinstance(spec, int):
        return np.full(n, spec, np.int32)
    table = length_table(spec)
    lens = np.array(sorted(table), np.int32)
    exact = n * np.array([table[k] for k in lens]) / sum(table.values())
    counts = np.floor(exact).astype(np.int64)
    counts[np.argsort(counts - exact, kind="stable")[:n - counts.sum()]] += 1
    out = np.repeat(lens, counts)
    rng.shuffle(out)
    return out


def random_variants(ref: np.ndarray, *, every_bp: int, mix: dict,
                    ins_len, del_span, site_pitch: int,
                    rng: np.random.Generator) -> Variants:
    """One variant per ``every_bp`` backbone bases on a ``site_pitch`` grid.

    ``mix`` gives the kinds' weights (e.g. snp 2, ins 1, del 1); a SNP
    carries the next base ``(ref + 1) % 4``, an insertion random bases
    after its site, a deletion drops bases after its site.  ``ins_len``
    and ``del_span`` are each an int, every indel's length, or a table
    ``{length: weight}`` over 1..15 whose shares every seed meets
    exactly.  Sites are distinct grid points at least the longest
    deletion plus 2 apart, so variants never overlap and a deletion never
    lands on another site; an int draws no numbers for the lengths.
    """
    n_ref = len(ref)
    n_var = n_ref // every_bp
    total = sum(mix.values())
    counts = {k: n_var * mix.get(k, 0) // total for k in KINDS}
    width = max(length_table(ins_len))
    reach = max(length_table(del_span))
    if site_pitch < reach + 2:
        raise ValueError(f"site pitch {site_pitch} lets a {reach} bp "
                         "deletion reach the next site")
    sites = np.arange(4, n_ref - max(8, reach + 2), site_pitch)
    n = min(sum(counts.values()), len(sites))
    pos = np.sort(rng.choice(sites, size=n, replace=False)).astype(np.int64)
    kind = np.concatenate([np.full(counts[k], i, np.int8)
                           for i, k in enumerate(KINDS)])[:n]
    rng.shuffle(kind)
    snp = kind == 0
    ins = kind == 1
    dele = kind == 2
    ins_lens = _lengths(ins_len, int(ins.sum()), rng)
    del_spans = _lengths(del_span, int(dele.sum()), rng)
    alt = np.zeros((n, width), np.int8)
    alt_len = np.zeros(n, np.int32)
    span = np.zeros(n, np.int32)
    alt[snp, 0] = (ref[pos[snp]] + 1) % 4
    alt_len[snp] = 1
    bases = rng.integers(0, 4, size=(int(ins.sum()), width), dtype=np.int8)
    alt[ins] = np.where(np.arange(width) < ins_lens[:, None], bases, 0)
    alt_len[ins] = ins_lens
    span[dele] = del_spans
    return Variants(pos, kind, alt, alt_len, span)


class Graph(NamedTuple):
    """Nodes ``first .. first + len(bases) - 1`` of a graph of ``total``."""

    bases: np.ndarray  # [N] int8
    succ: np.ndarray  # [N] uint32 hop bits
    backbone: np.ndarray  # [N] int64 backbone coordinate (-1 on alt nodes)
    first: int  # node id of bases[0]
    total: int  # nodes in the whole graph


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
    return Graph(bases, succ, backbone, 0, n)


class Coords(NamedTuple):
    """Whole-graph node ids without the graph: backbone base ``p`` is node
    ``p`` plus the alt nodes of the variants before it."""

    pos: np.ndarray  # [V] variant sites
    before: np.ndarray  # [V + 1] alt nodes of the variants before each
    site: np.ndarray  # [V] node id of each site's backbone base
    total: int  # nodes in the whole graph

    def node(self, p):
        """Node id of backbone base(s) ``p``."""
        return p + self.before[np.searchsorted(self.pos, p, side="left")]

    def base_at(self, node: int) -> int:
        """The backbone base of ``node``, or the site of the variant whose
        alt node it is."""
        i = int(np.searchsorted(self.site, node, side="right")) - 1
        if i < 0:
            return int(node)
        off = int(node) - int(self.site[i])
        alt = int(self.before[i + 1] - self.before[i])
        return int(self.pos[i]) + (off - alt if off > alt else 0)


def coords(n_ref: int, v: Variants) -> Coords:
    before = np.concatenate([[0], np.cumsum(v.alt_len, dtype=np.int64)])
    return Coords(v.pos, before, v.pos + before[:-1], n_ref + int(before[-1]))


def local_graph(ref: np.ndarray, v: Variants, c: Coords, first: int,
                last: int) -> Graph:
    """The whole graph's nodes ``first..last``, under its node ids.

    Built from whole backbone bases with ``HOP_LIMIT`` more on each side;
    every node and every edge between two of its nodes is the whole
    graph's, and edges that leave it are cut (a deletion that lands past
    its end is left out).
    """
    first = min(max(first, 0), c.total - 1)
    last = min(max(last, first), c.total - 1)
    lo = max(c.base_at(first) - HOP_LIMIT, 0)
    hi = min(c.base_at(last) + 1 + HOP_LIMIT, len(ref))
    i0, i1 = np.searchsorted(v.pos, [lo, hi])
    part = Variants(*(a[i0:i1] for a in v))
    keep = (part.kind != 2) | (part.pos + part.span + 1 < hi)
    part = Variants(part.pos[keep] - lo, *(a[keep] for a in part[1:]))
    g = build_graph(ref[lo:hi], part)
    backbone = np.where(g.backbone >= 0, g.backbone + lo, -1)
    return Graph(g.bases, g.succ, backbone, int(c.node(lo)), c.total)


def answer_graph(ref: np.ndarray, v: Variants, c: Coords, path: np.ndarray,
                 span: int) -> tuple[Graph, int]:
    """The local graph that checking a graph answer reads, and the first
    node of its anchored window: the path's nodes up to its first step
    too long to be an edge, and ``span`` nodes from its first node."""
    nodes = path[path >= 0].astype(np.int64)
    if not len(nodes):
        return local_graph(ref, v, c, 0, span - 1), 0
    hop = np.diff(nodes) - 1
    far = np.nonzero((hop < 0) | (hop >= HOP_LIMIT))[0]
    chain = nodes[:int(far[0]) + 1 if len(far) else len(nodes)]
    start = int(nodes[0])
    return (local_graph(ref, v, c, int(min(chain.min(), start)),
                        int(max(chain.max(), start + span - 1))), start)


class Haplotype(NamedTuple):
    """One sample's sequence: the backbone with the alt allele taken at the
    variants ``taken``, whose sites lie at ``at`` on ``seq``."""

    seq: np.ndarray  # int8 bases
    taken: Variants
    at: np.ndarray  # [K] haplotype position of each taken site's base

    def _site(self, q: np.ndarray):
        """For each ``q``: the last taken site at or before it (a SNP at
        -1 where there is none) as ``(pos, kind, alt_len, span)``, and
        ``q``'s offset from that site's base."""
        i = np.searchsorted(self.at, q, side="right")
        t = self.taken
        pos, kind, alt_len, span, at = (
            np.concatenate([[fill], a])[i] for a, fill in
            ((t.pos, -1), (t.kind, 0), (t.alt_len, 0), (t.span, 0),
             (self.at, -1)))
        return pos, kind, alt_len, span, q - at

    def _locate(self, q):
        """``(on_alt, pos, off, bb)``: whether ``q`` is an alt base of the
        site at ``pos``, its offset, and else its backbone coordinate."""
        pos, kind, alt_len, span, off = self._site(np.asarray(q, np.int64))
        on_alt = (((kind == 0) & (off == 0))
                  | ((kind == 1) & (off >= 1) & (off <= alt_len)))
        shift = np.where(kind == 1, -alt_len, np.where(kind == 2, span, 0))
        bb = np.where(off == 0, pos, pos + off + shift)
        return on_alt, pos, off, bb

    def first_backbone(self, q: np.ndarray) -> np.ndarray:
        """Backbone coordinate of the first base at or after each ``q``
        that lies on the backbone."""
        on_alt, pos, _, bb = self._locate(q)
        return np.where(on_alt, pos + 1, bb)

    def nodes(self, c: Coords, q: np.ndarray) -> np.ndarray:
        """Whole-graph node id of each haplotype position ``q``."""
        on_alt, pos, off, bb = self._locate(q)
        return np.where(on_alt, c.node(pos) + np.maximum(off, 1),
                        c.node(np.maximum(bb, 0)))


def haplotype(ref: np.ndarray, v: Variants, alt_share: float,
              rng: np.random.Generator) -> Haplotype:
    """A haplotype that takes each variant's alt allele with probability
    ``alt_share``: a SNP's base replaces its site's, an insertion's bases
    follow its site, a deletion's span is gone."""
    if np.any(v.pos[1:] <= v.pos[:-1] + v.span[:-1]):
        raise ValueError("variants overlap")
    take = rng.random(len(v.pos)) < alt_share
    t = Variants(*(a[take] for a in v))
    seq = ref.copy()
    snp, ins, dele = (t.kind == k for k in range(3))
    seq[t.pos[snp]] = t.alt[snp, 0]
    spans = t.span[dele]
    gone = (np.repeat(t.pos[dele] + 1 - np.cumsum(spans) + spans, spans)
            + np.arange(int(spans.sum())))
    keep = np.ones(len(ref), bool)
    keep[gone] = False
    seq = seq[keep]
    lens = t.alt_len[ins]
    dropped = np.concatenate([[0], np.cumsum(spans)])
    before = dropped[np.searchsorted(t.pos[dele], t.pos[ins] + 1)]
    alt = t.alt[ins]
    seq = np.insert(seq, np.repeat(t.pos[ins] + 1 - before, lens),
                    alt[np.arange(alt.shape[1]) < lens[:, None]])
    shift = np.where(ins, t.alt_len, 0) - np.where(dele, t.span, 0)
    at = t.pos + np.concatenate([[0], np.cumsum(shift)[:-1]])
    return Haplotype(seq, t, at.astype(np.int64))


def spelled_read(hap: Haplotype, c: Coords, start: int, length: int,
                 profile: dict, rng: np.random.Generator):
    """A read drawn from ``hap`` at ``start`` with Mason-style edits
    (``profile`` as in a traffic mix), and the answer that spells it:
    ``(read, (position, distance, ops, path))``."""
    src = hap.seq[start:start + length]
    nodes = hap.nodes(c, np.arange(start, start + length))
    err = rng.random(length) < profile["rate"]
    kind = rng.random(length)
    cut = (profile["sub"], profile["sub"] + profile["ins"])
    read, ops, path = [], [], []
    for j in range(length):
        if err[j] and cut[0] <= kind[j] < cut[1]:  # a base put before
            read.append(int(rng.integers(4)))
            ops.append(OP_I)
            path.append(-1)
        if err[j] and kind[j] >= cut[1]:
            ops.append(OP_D)
        elif err[j] and kind[j] < cut[0]:
            read.append((int(src[j]) + int(rng.integers(1, 4))) % 4)
            ops.append(OP_X)
        else:
            read.append(int(src[j]))
            ops.append(OP_M)
        path.append(int(nodes[j]))
    ops = np.array(ops, np.int8)
    return (np.array(read, np.int8),
            (int(hap.first_backbone(start)), int(np.sum(ops != OP_M)), ops,
             np.array(path, np.int64)))


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


def stack_windows(rows: list) -> tuple[np.ndarray, np.ndarray]:
    """One ``[R, T]`` pair from one-row ``windows`` results, padded as
    ``windows`` pads a shorter row."""
    t = max(b.shape[1] for b, _ in rows)
    bases = np.full((len(rows), t), SENTINEL, np.int8)
    succ = np.zeros((len(rows), t), np.uint32)
    for r, (b, s) in enumerate(rows):
        bases[r, :b.shape[1]] = b[0]
        succ[r, :s.shape[1]] = s[0]
    return bases, succ


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
    chain = np.zeros((r, t), bool)  # node j - 1 has an edge to node j
    chain[:, 1:] = (succ[:, :-1] & 1) == 1
    # longer edges, few: (row, from, to) for hops 1..15
    rr, src, hop = np.nonzero(((succ[:, :, None] >> np.arange(
        1, HOP_LIMIT, dtype=np.uint32)) & 1) == 1)
    dst = src + hop + 2
    inside = dst < t
    rr, src, dst = rr[inside], src[inside], dst[inside]
    inf = np.int64(1 << 28)
    # a chain run's closure is a cumulative minimum of ``a[k] - k``; the
    # runs are kept apart by an offset larger than any cost
    run = np.cumsum(~chain, axis=1) * np.int64(1 << 32) + np.arange(t)

    def close(a):  # deletions: walk edges without consuming read bases
        while True:
            a = np.minimum.accumulate(a - run, axis=1) + run
            b = a.copy()
            np.minimum.at(b, (rr, dst), a[rr, src] + 1)
            if np.array_equal(a, b):
                return a
            a = b

    out = m.astype(np.int64).copy()  # all insertions consume no node
    row = np.full((r, t), inf, np.int64)
    row[:, 0] = 1  # empty read prefix, start node deleted
    row = close(row)
    for i in range(1, mmax + 1):
        cost = (pat[:, i - 1, None] != bases).astype(np.int64)
        cur = row + 1  # insertion: read base i against node j again
        cur[:, 0] = np.minimum(cur[:, 0],
                               np.minimum(i + 1, i - 1 + cost[:, 0]))
        cur[:, 1:] = np.where(chain[:, 1:], np.minimum(
            cur[:, 1:], row[:, :-1] + cost[:, 1:]), cur[:, 1:])
        np.minimum.at(cur, (rr, dst), row[rr, src] + cost[rr, dst])
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
    """None when a graph answer walks edges and spells ``read``.

    ``g`` may be a local graph (``answer_graph``): it has to hold the
    path's nodes up to its first step too long to be an edge."""
    consumes = np.isin(ops, (OP_M, OP_X, OP_D))
    if not np.array_equal(path >= 0, consumes):
        return "path entries do not match the node-consuming ops"
    nodes = path[consumes].astype(np.int64)
    if len(nodes) == 0:
        return "the path consumes no node"
    if nodes.min() < 0 or nodes.max() >= g.total:
        return "path node outside the graph"
    hop = nodes[1:] - nodes[:-1] - 1
    ok = (hop >= 0) & (hop < HOP_LIMIT)
    chained = len(ok) if ok.all() else int(np.argmin(ok))
    local = nodes[:chained + 1] - g.first  # steps up to the first too long
    if local.min() < 0 or local.max() >= len(g.bases):
        raise ValueError("the local graph does not hold the path")
    ok[:chained] = ((g.succ[local[:-1]] >> hop[:chained].astype(np.uint32))
                    & 1) == 1
    if not ok.all():
        k = int(np.argmin(ok))
        return f"path steps {nodes[k]}->{nodes[k + 1]} along no edge"
    bb = g.backbone[local]
    first = bb[bb >= 0]
    want = int(first[0]) if len(first) else -1
    if want != position:
        return f"position {position}, first backbone node of the path {want}"
    return cigar_error(ops, read, g.bases[local], distance)
