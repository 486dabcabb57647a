"""Windowed BitAlign: sequence-to-graph alignment as chained DC+TB windows.

The paper's BitAlign (§6.7) is GenASM's divide-and-conquer dataflow with
one generalization: scanning the linearized subgraph in reverse
topological order, the "previous text character" status bitvectors are
the AND-combination of every successor's bitvectors within the hopBits
window (Figure 6-9).  This module runs that generalized DC inside the
*same* window loop as the linear aligner — `core/genasm.window_commit`
is shared, the traceback mirrors `core/genasm_tb.window_tb_r` bit for
bit.  A window holding a variant differs from a linear one in two ways,
both for the alt nodes a path skips: it spans `text_window` nodes, ``w``
plus room for skipped alleles, and the read's tail past its end is
pre-matched.  A window whose first ``w`` nodes form a chain has nothing
to skip and runs as a linear window (`window_view`), so on a degenerate
(pure-backbone) graph the emitted distances, CIGARs and text advances
are **bit-identical** to the `lax` backend — the graph conformance
suite's anchor.

Graph windows travel through the uniform dispatch signature as **packed
graph text**: one uint32 per node, base id in the low 8 bits and the
window-masked hopBits in bits 8..8+HOP_LIMIT (19 bits used, so the
packing stays inside JAX's default 32-bit world).  ``pack_linear_text``
packs a plain int8 text as a hop-0 chain, which is how the graph
backends accept the linear conformance inputs unchanged.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.bitvector import (SENTINEL, get_bit, msb, n_words,
                                  pattern_bitmasks, shl1)
from repro.core.genasm import (AlignResult, GenASMConfig, pad_pattern,
                               window_commit)
from repro.core.genasm_tb import OP_D, OP_I, OP_M, OP_PAD, OP_X
from repro.core.segram.graph import HOP_LIMIT

_HOP_MASK = (1 << HOP_LIMIT) - 1
# sentinel pad node: matches nothing, chains to its neighbour (hop 0) so a
# packed linear text and the linear aligner's sentinel tail agree bitwise
SENT_NODE = (1 << 8) | SENTINEL


def pack_graph_text(bases: jnp.ndarray, succ_bits: jnp.ndarray) -> jnp.ndarray:
    """[..., n] (int8 bases, uint32 hopBits) -> packed uint32 graph text."""
    b = jnp.asarray(bases).astype(jnp.uint32) & jnp.uint32(0xFF)
    s = jnp.asarray(succ_bits).astype(jnp.uint32) & jnp.uint32(_HOP_MASK)
    return (s << 8) | b


def pack_linear_text(text: jnp.ndarray) -> jnp.ndarray:
    """Pack a plain int8 text as a hop-0 chain graph."""
    text = jnp.asarray(text)
    return pack_graph_text(text, jnp.ones(text.shape, jnp.uint32))


def unpack_graph_text(gtext: jnp.ndarray):
    """Packed uint32 graph text -> (bases int8, succ_bits uint32)."""
    base = (gtext & jnp.uint32(0xFF)).astype(jnp.int8)
    succ = (gtext >> 8) & jnp.uint32(_HOP_MASK)
    return base, succ


def text_window(cfg: GenASMConfig) -> int:
    """Nodes in one graph window: the ``w`` a linear window spans, plus
    ``2 * HOP_LIMIT`` of room.  A path skips the alt nodes of variants it
    does not take (an insertion allele of up to ``HOP_LIMIT - 1`` nodes, a
    SNP's alt node) and the backbone nodes of deletions it does; without
    the room, skipped nodes eat into the ``o`` characters of lookahead
    past the window's commit point, and the window can take a branch into
    an allele the read does not carry, paying for every node of it.  The
    room holds 24 skipped allele nodes and 8 read deletions, the stated
    distance bound's condition."""
    return cfg.w + 2 * HOP_LIMIT


def window_view(sub_g: jnp.ndarray, p_rest, w: int):
    """Unpack ``[..., text_window]`` packed window nodes for the DC and
    traceback: ``(bases, succ, p_len)``, where ``p_len`` is how much of
    the ``w``-character sub-pattern the DC reads before the pre-matched
    tail (``p_rest``, the read characters left, clipped to ``w``).

    A window whose first ``w`` nodes form a chain (hop-0 edges only) has
    nothing a path can skip, so it is run as a linear window, bit for bit:
    node ``w - 1`` loses its edge, as a linear window ends there, and the
    read's tail is read as the linear aligner reads it.
    """
    bases, succ = unpack_graph_text(sub_g)
    chain = jnp.all(succ[..., :w] == 1, axis=-1)
    succ = succ.at[..., w - 1].set(
        jnp.where(chain, jnp.uint32(0), succ[..., w - 1]))
    p_len = jnp.where(chain, w, jnp.clip(p_rest, 0, w)).astype(jnp.int32)
    return bases, succ, p_len


def pad_graph_text(gtext: jnp.ndarray, t_len, cap: int, cfg: GenASMConfig):
    """Pad/trim a packed graph-text buffer to ``cap`` plus one window
    (`text_window`) with sentinel chain nodes after ``t_len`` (the graph
    twin of `genasm.pad_text`)."""
    size = cap + text_window(cfg)
    buf = jnp.full((size,), SENT_NODE, jnp.uint32)
    buf = lax.dynamic_update_slice(buf, jnp.asarray(gtext, jnp.uint32)[:size],
                                   (0,))
    idx = jnp.arange(size)
    return jnp.where(idx < t_len, buf, jnp.uint32(SENT_NODE))


def _graph_buf_cap(p_cap: int, cfg: GenASMConfig) -> int:
    # a window's node advance can overshoot the linear commit by up to one
    # hop, so the buffer carries HOP_LIMIT extra nodes per window
    return p_cap + cfg.n_windows(p_cap) * (cfg.commit + HOP_LIMIT)


@partial(jax.jit, static_argnames=("w", "k"))
def window_dc_graph(bases: jnp.ndarray, succ: jnp.ndarray,
                    sub_pattern: jnp.ndarray, p_len, *, w: int, k: int):
    """BitAlign DC over one subgraph window (R-only store).

    ``bases``/``succ``: the window's nodes, ``w`` or more (hops past the
    window end fall on the boundary via the hop ring buffer, no masking
    needed).  Only the first ``p_len`` characters of the ``w``-character
    ``sub_pattern`` are read: its tail is pre-matched everywhere
    (`bitalign._tail_mask`), so the read's end may meet the window's last
    node, and alt nodes a path skips over never push the tail past the
    window end.
    Returns ``(d_min int32, store [nodes, k+1, nw] uint32)`` — ``d_min``
    is anchored at node 0, ``store[i]`` the status rows R of node ``i``.
    On a hop-0 chain of ``w`` nodes with ``p_len == w`` this equals
    `core/genasm_dc.window_dc_r` bitwise.
    """
    from repro.core.segram.bitalign import _tail_mask

    nw = n_words(w)
    pm = pattern_bitmasks(sub_pattern, w)
    H = HOP_LIMIT
    tail = _tail_mask(p_len, w)
    boundary = jnp.broadcast_to(tail, (k + 1, nw))

    def step(hist, inputs):
        # hist: [H, k+1, nw] — hist[h] = R of node i + 1 + h
        base, sb = inputs
        hop_ok = ((sb >> jnp.arange(H, dtype=jnp.uint32)) & 1).astype(bool)
        masked = jnp.where(hop_ok[:, None, None], hist, boundary[None])
        comb = masked[0]
        for h in range(1, H):
            comb = comb & masked[h]  # [k+1, nw]; the tail when no successor
        cur_pm = pm[base]
        rows = [(shl1(comb[0]) | cur_pm) & tail]
        for d in range(1, k + 1):
            D = comb[d - 1]
            S = shl1(comb[d - 1])
            I = shl1(rows[d - 1])
            M = shl1(comb[d]) | cur_pm
            rows.append(D & S & I & M & tail)
        R = jnp.stack(rows)  # [k+1, nw]
        return jnp.concatenate([R[None], hist[:-1]], axis=0), R

    hist0 = jnp.broadcast_to(boundary, (H, k + 1, nw))
    _, rows_rev = lax.scan(
        step, hist0, (bases[::-1].astype(jnp.int32), succ[::-1]))
    store = rows_rev[::-1]  # [w, k+1, nw], indexed by node position
    m = msb(store[0])
    found = m == 0
    d_min = jnp.where(jnp.any(found), jnp.argmax(found), k + 1).astype(jnp.int32)
    return d_min, store


@partial(jax.jit, static_argnames=("m_bits", "k"))
def bitalign_search(bases: jnp.ndarray, succ: jnp.ndarray,
                    pattern: jnp.ndarray, p_len, *, m_bits: int, k: int):
    """Distances-only whole-pattern BitAlign over a subgraph window.

    The graph mapper's pre-alignment filter: ``dists[i]`` is the minimum
    ``d ≤ k`` aligning the full (tail-masked) pattern to a path starting
    at node ``i`` (``k + 1`` when none) — one pass both *filters* a
    candidate window and *refines* its anchor node (argmin), exactly how
    the linear mapper uses `genasm_dc.bitap_search`.  Bitwise identical
    to the dists output of `repro.kernels.bitalign.bitalign_dc_batch`
    (the tail handling mirrors the kernel), which the graph conformance
    suite pins — the mapper may take either path per backend.
    """
    from repro.core.segram.bitalign import _tail_mask

    nw = n_words(m_bits)
    pm = pattern_bitmasks(pattern, m_bits)
    H = HOP_LIMIT
    tail = _tail_mask(p_len, m_bits)  # [nw]
    tail_rows = jnp.broadcast_to(tail, (k + 1, nw))

    def step(hist, inputs):
        base, sb = inputs
        hop_ok = ((sb >> jnp.arange(H, dtype=jnp.uint32)) & 1).astype(bool)
        masked = jnp.where(hop_ok[:, None, None], hist, tail_rows[None])
        comb = masked[0]
        for h in range(1, H):
            comb = comb & masked[h]
        cur_pm = pm[base]
        rows = [(shl1(comb[0]) | cur_pm) & tail]
        for d in range(1, k + 1):
            D = comb[d - 1]
            S = shl1(comb[d - 1])
            I = shl1(rows[d - 1])
            M = shl1(comb[d]) | cur_pm
            rows.append(D & S & I & M & tail)
        R = jnp.stack(rows)
        m = msb(R)
        found = m == 0
        d_i = jnp.where(jnp.any(found), jnp.argmax(found), k + 1
                        ).astype(jnp.int32)
        return jnp.concatenate([R[None], hist[:-1]], axis=0), d_i

    hist0 = jnp.broadcast_to(tail_rows, (H, k + 1, nw))
    _, dists_rev = lax.scan(
        step, hist0, (bases[::-1].astype(jnp.int32), succ[::-1]))
    return dists_rev[::-1]


@partial(jax.jit, static_argnames=("w", "o", "k", "affine"))
def window_tb_graph(store: jnp.ndarray, succ: jnp.ndarray, bases: jnp.ndarray,
                    pm: jnp.ndarray, d_start, cap_p, *, w: int, o: int,
                    k: int, affine: bool = True):
    """Graph traceback over one window's R-only store.

    The check-vector derivation mirrors `genasm_tb.window_tb_r` with the
    single-successor row replaced by the hop combine: an op that consumes
    a node is valid iff *some* in-window successor's R continues the
    0-chain, and the successor actually taken (lowest qualifying hop) is
    how the walk advances through the linearization — that choice is the
    node path GAF reports.

    ``store`` may span more nodes than the ``w``-character sub-pattern
    (`text_window`); the commit stays within the first ``w - o`` nodes.
    Returns ``(pc, tc, err_used, ops [2*(w-o)] int8, n_ops,
    nodes [2*(w-o)] int32 window-local node per op (-1 for I), stuck)``.
    ``tc`` is the node advance for the next window (hops included).
    """
    tw = store.shape[0]  # window nodes
    max_steps = 2 * (w - o)
    cap_t = jnp.int32(w - o)
    cap_p = jnp.asarray(cap_p, jnp.int32)
    H = HOP_LIMIT
    hop_rng = jnp.arange(H)
    no_hops = jnp.zeros((H,), bool)

    def succ_rows(ti, de):
        """[H, nw] successor R rows (all-ones past the window boundary)."""
        pos = jnp.clip(ti + 1 + hop_rng, 0, tw - 1)
        rows = store[pos, de]
        in_w = (ti + 1 + hop_rng) < tw
        return jnp.where(in_w[:, None], rows, jnp.uint32(0xFFFFFFFF))

    def body(_, st):
        patternI, textI, curError, prev_op, pc, tc, n_ops, ops, nodes, stuck = st
        active = (pc < cap_p) & (tc < cap_t) & (patternI >= 0) & (~stuck)
        ti = jnp.clip(textI, 0, tw - 1)
        de = jnp.clip(curError, 0, k)
        dem1 = jnp.clip(curError - 1, 0, k)
        pi = jnp.clip(patternI, 0, w - 1)
        pim1 = jnp.maximum(pi - 1, 0)
        at0 = pi == 0  # shl1's shifted-in 0: the check bit is always clear

        smask = ((succ[ti] >> hop_rng.astype(jnp.uint32)) & 1).astype(bool)
        rows_d = succ_rows(ti, de)
        rows_dm1 = succ_rows(ti, dem1)

        def bits0(rows, b):
            return jax.vmap(lambda v: get_bit(v, b))(rows) == 0

        m_hops = smask & (at0 | bits0(rows_d, pim1))
        s_hops = smask & (at0 | bits0(rows_dm1, pim1))
        d_hops = smask & bits0(rows_dm1, pi)

        pm_bit = get_bit(pm[bases[ti]], pi) == 0
        mbit = pm_bit & (at0 | jnp.any(m_hops))
        sbit = at0 | jnp.any(s_hops)
        ibit = jnp.where(at0, True, get_bit(store[ti, dem1], pim1) == 0)
        dbit = jnp.any(d_hops)

        has_err = curError > 0
        m_ok = mbit
        s_ok = sbit & has_err
        i_ok = ibit & has_err
        d_ok = dbit & has_err

        if affine:
            cands = jnp.stack([
                i_ok & (prev_op == OP_I), d_ok & (prev_op == OP_D),
                m_ok, s_ok, i_ok, d_ok,
            ])
            codes = jnp.array([OP_I, OP_D, OP_M, OP_X, OP_I, OP_D], jnp.int32)
            hopsets = jnp.stack([no_hops, d_hops, m_hops, s_hops, no_hops,
                                 d_hops])
        else:
            cands = jnp.stack([m_ok, s_ok, i_ok, d_ok])
            codes = jnp.array([OP_M, OP_X, OP_I, OP_D], jnp.int32)
            hopsets = jnp.stack([m_hops, s_hops, no_hops, d_hops])

        any_ok = jnp.any(cands)
        sel = jnp.argmax(cands)
        op = codes[sel]
        new_stuck = stuck | (active & ~any_ok)
        take = active & any_ok

        consume_p = take & ((op == OP_M) | (op == OP_X) | (op == OP_I))
        consume_t = take & ((op == OP_M) | (op == OP_X) | (op == OP_D))
        err_dec = take & (op != OP_M)
        # lowest qualifying hop; falls back to hop 0 (the chain neighbour)
        # when the walk ends on this op and no successor constraint applies
        h_star = jnp.argmax(hopsets[sel]).astype(jnp.int32)
        adv = jnp.where(consume_t, 1 + h_star, 0)

        ops = ops.at[n_ops].set(jnp.where(take, op.astype(jnp.int8), ops[n_ops]))
        nodes = nodes.at[n_ops].set(
            jnp.where(consume_t, ti, jnp.where(take, -1, nodes[n_ops])))
        return (
            patternI - consume_p.astype(jnp.int32),
            textI + adv,
            curError - err_dec.astype(jnp.int32),
            jnp.where(take, op, prev_op),
            pc + consume_p.astype(jnp.int32),
            tc + adv,
            n_ops + take.astype(jnp.int32),
            ops,
            nodes,
            new_stuck,
        )

    st0 = (
        jnp.int32(w - 1),  # patternI: MSB = pattern[0]
        jnp.int32(0),  # textI (window-local node)
        d_start.astype(jnp.int32),
        jnp.int32(OP_PAD),  # prev_op
        jnp.int32(0),  # pc
        jnp.int32(0),  # tc
        jnp.int32(0),  # n_ops
        jnp.full((max_steps,), OP_PAD, jnp.int8),
        jnp.full((max_steps,), -1, jnp.int32),
        jnp.asarray(False),
    )
    _, _, curError, _, pc, tc, n_ops, ops, nodes, stuck = lax.fori_loop(
        0, max_steps, body, st0)
    err_used = d_start.astype(jnp.int32) - curError
    return pc, tc, err_used, ops, n_ops, nodes, stuck


def _scatter_windows(vals_w, n_ops_w, cap: int, fill, dtype):
    """Concatenate per-window op-aligned buffers into one [cap] buffer."""
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(n_ops_w)[:-1]])
    max_steps = vals_w.shape[-1]
    step_idx = jnp.arange(max_steps)[None, :]
    valid = step_idx < n_ops_w[:, None]
    pos = jnp.where(valid, offsets[:, None] + step_idx, cap)
    out = jnp.full((cap,), fill, dtype)
    return out.at[pos.reshape(-1)].set(vals_w.reshape(-1), mode="drop")


@partial(jax.jit, static_argnames=("cfg", "p_cap", "emit_cigar"))
def graph_align(
    gtext: jnp.ndarray,
    pattern: jnp.ndarray,
    p_len: jnp.ndarray,
    t_len: jnp.ndarray,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int | None = None,
    emit_cigar: bool = True,
) -> AlignResult:
    """Align ``pattern[:p_len]`` to the packed subgraph ``gtext[:t_len]``,
    anchored at node 0 (the graph twin of `core/genasm.align`).

    Semi-global: the pattern must be fully consumed, trailing graph is
    free.  ``AlignResult.nodes`` carries the window-relative node offset
    each op consumed (-1 for insertions) — the path GAF reports.
    """
    if p_cap is None:
        p_cap = int(pattern.shape[-1])
    n_win = cfg.n_windows(p_cap)
    max_steps = 2 * cfg.commit
    w, o, k = cfg.w, cfg.o, cfg.k
    tw = text_window(cfg)

    pat = pad_pattern(pattern, p_len, p_cap, cfg)
    gbuf = pad_graph_text(gtext, t_len, _graph_buf_cap(p_cap, cfg), cfg)

    def window_step(carry, _):
        cur_p, cur_t = carry[0], carry[1]
        sub_p = lax.dynamic_slice(pat, (cur_p,), (w,))
        sub_g = lax.dynamic_slice(gbuf, (cur_t,), (tw,))
        bases, succ, p_win = window_view(sub_g, p_len - cur_p, w)
        d_min, store = window_dc_graph(bases, succ, sub_p, p_win, w=w, k=k)
        cap_p = jnp.minimum(jnp.int32(cfg.commit), p_len - cur_p)
        pm = pattern_bitmasks(sub_p, w)
        pc, tc, err, ops, n_ops, nodes, stuck = window_tb_graph(
            store, succ, bases, pm, jnp.minimum(d_min, k), cap_p,
            w=w, o=o, k=k, affine=cfg.affine)
        new_carry, n_emit = window_commit(
            carry, d_min=d_min, pc=pc, tc=tc, err=err, n_ops=n_ops,
            stuck=stuck, p_len=p_len, k=k)
        nodes = jnp.where(nodes >= 0, nodes + cur_t, -1)
        return new_carry, (ops, nodes, n_emit)

    init = (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.asarray(False),
            p_len <= 0)
    (fin_p, fin_t, dist, failed, done), (ops_w, nodes_w, n_ops_w) = lax.scan(
        window_step, init, None, length=n_win)
    failed = failed | (~done)

    if emit_cigar:
        cap = n_win * max_steps
        out_ops = _scatter_windows(ops_w, n_ops_w, cap, OP_PAD, jnp.int8)
        out_nodes = _scatter_windows(nodes_w, n_ops_w, cap, -1, jnp.int32)
    else:
        out_ops = jnp.full((1,), OP_PAD, jnp.int8)
        out_nodes = None
    n_total = jnp.sum(n_ops_w)

    return AlignResult(
        distance=jnp.where(failed, jnp.int32(-1), dist),
        ops=out_ops,
        n_ops=n_total,
        text_consumed=fin_t,
        failed=failed,
        nodes=out_nodes,
    )
