"""Pallas TPU kernels for BitAlign sequence-to-graph DC (paper §6.8.2).

Same lane strategy as the GenASM-DC kernels: one (read × subgraph window)
alignment per VPU lane, lane-major layout (batch on the last axis), a
sequential reverse-topological node scan.  The hop queue of the BitAlign
PE (Figure 6-8) is a VMEM ring of the last ``HOP_LIMIT`` nodes' status
rows: node ``j`` lives in slot ``j mod HOP_LIMIT``, and a node's hopBits
are rotated so bit ``s`` selects slot ``s`` directly.

Two kernels share the scan:

* `bitalign_dc_batch` — per-node distances and status rows (R-only
  storage, the §Perf #8 scheme generalized to graphs): the graph align
  backend's DC.
* `bitalign_filter_batch` — the graph mapper's pre-alignment filter:
  only the minimum distance over the first ``span`` nodes and its
  (first) node, no per-node output at all.

Graph nodes enter packed (`graph.windowed.pack_graph_text` layout: base
in bits 0..7, hopBits above), the PM table and the pattern-tail mask
are built outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitvector import NUM_CHARS, WORD_BITS
from repro.core.segram.graph import HOP_LIMIT

from .genasm_dc import (DEFAULT_BT, batch_tile, dc_rows,
                        load_pm, min_distance, pm_lanes, select_pm, to_lanes)

_HOP_MASK = (1 << HOP_LIMIT) - 1


def _tail_mask_wm(p_lens: jnp.ndarray, m_bits: int, nw: int) -> jnp.ndarray:
    """[nw, B] uint32 tail masks (low (m_bits - p_len) bits cleared)."""
    pad = (m_bits - p_lens).astype(jnp.int32)  # [B]
    out = []
    for wd in range(nw):
        bits_below = jnp.clip(pad - 32 * wd, 0, 32)
        low = jnp.where(
            bits_below >= 32,
            jnp.uint32(0xFFFFFFFF),
            (jnp.uint32(1) << bits_below.astype(jnp.uint32)) - jnp.uint32(1),
        )
        out.append(~low)
    return jnp.stack(out)  # [nw, B]


def _scan(node_ref, pm_ref, tail_ref, ring_ref, comb_ref, *, n: int, k: int,
          nw: int, emit):
    """Reverse node scan; ``emit(i, R_rows, carry) -> carry`` per node."""
    H = HOP_LIMIT
    pm = load_pm(pm_ref, nw)
    tail = [tail_ref[pl.ds(j, 1), :] for j in range(nw)]
    tail_block = tail_ref[...]  # [(k+1)·nw, BT]: tail repeated per row
    for s in range(H):
        ring_ref[s] = tail_block  # nodes past the window end

    def step(s, carry):
        i = n - 1 - s
        x = node_ref[pl.ds(i, 1), :]  # [1, BT] packed node
        sb = (x >> 8) & jnp.uint32(_HOP_MASK)
        r = ((i + 1) % H).astype(jnp.uint32)
        rot = ((sb << r) | (sb >> (jnp.uint32(H) - r))) & jnp.uint32(
            _HOP_MASK)  # bit s: successor in ring slot s
        comb = tail_block
        for slot in range(H):
            nok = ((rot >> slot) & 1) - jnp.uint32(1)  # 0 if hop, else ~0
            comb = comb & (ring_ref[slot] | nok)
        comb_ref[...] = comb
        rows_old = [[comb_ref[pl.ds(d * nw + j, 1), :] for j in range(nw)]
                    for d in range(k + 1)]
        cur_pm = select_pm(pm, (x & jnp.uint32(0xFF)).astype(jnp.int32))
        rows, _ = dc_rows(rows_old, cur_pm, k, mask=tail)
        slot_i = i % H
        for d in range(k + 1):
            for j in range(nw):
                ring_ref[slot_i, pl.ds(d * nw + j, 1), :] = rows[d][j]
        return emit(i, rows, carry)

    return step


def _dc_kernel(node_ref, pm_ref, tail_ref, dists_ref, r_ref, ring_ref,
               comb_ref, *, n: int, k: int, nw: int):
    def emit(i, rows, carry):
        for d in range(k + 1):
            for j in range(nw):
                r_ref[i, pl.ds(d * nw + j, 1), :] = rows[d][j]
        dists_ref[pl.ds(i, 1), :] = min_distance(rows, k)
        return carry

    step = _scan(node_ref, pm_ref, tail_ref, ring_ref, comb_ref, n=n, k=k,
                 nw=nw, emit=emit)
    lax.fori_loop(0, n, step, 0)


def _filter_kernel(node_ref, pm_ref, tail_ref, best_ref, off_ref, ring_ref,
                   comb_ref, *, n: int, k: int, nw: int, span: int):
    def emit(i, rows, carry):
        best, off = carry
        d_i = min_distance(rows, k)
        # descending scan: ``<=`` keeps the first (lowest) minimal node
        upd = (d_i <= best) & (i < span)
        return jnp.where(upd, d_i, best), jnp.where(upd, i, off)

    step = _scan(node_ref, pm_ref, tail_ref, ring_ref, comb_ref, n=n, k=k,
                 nw=nw, emit=emit)
    bt = node_ref.shape[-1]
    best, off = lax.fori_loop(
        0, n, step, (jnp.full((1, bt), k + 1, jnp.int32),
                     jnp.zeros((1, bt), jnp.int32)))
    best_ref[...] = best
    off_ref[...] = off


def _lane_inputs(bases, succ_bits, patterns, p_lens, *, m_bits, k, bt):
    """Packed nodes ``[N, B']``, PM ``[5·nw, B']`` and the per-row tail
    mask ``[(k+1)·nw, B']`` (padded lanes: sentinel chain, full pattern)."""
    nw = m_bits // WORD_BITS
    node = ((succ_bits.astype(jnp.uint32) & jnp.uint32(_HOP_MASK)) << 8) \
        | (bases.astype(jnp.uint32) & jnp.uint32(0xFF))
    node = to_lanes(node, bt, 4)
    pm = pm_lanes(patterns, m_bits, bt)
    tail = jnp.tile(_tail_mask_wm(p_lens, m_bits, nw), (k + 1, 1))
    tail = to_lanes(tail.T, bt, 0xFFFFFFFF)
    return node, pm, tail


def _specs(n: int, nw: int, k: int, bt: int):
    rows = (k + 1) * nw
    in_specs = [
        pl.BlockSpec((n, bt), lambda i: (0, i)),
        pl.BlockSpec((NUM_CHARS * nw, bt), lambda i: (0, i)),
        pl.BlockSpec((rows, bt), lambda i: (0, i)),
    ]
    scratch = [pltpu.VMEM((HOP_LIMIT, rows, bt), jnp.uint32),
               pltpu.VMEM((rows, bt), jnp.uint32)]
    return in_specs, scratch


@functools.partial(
    jax.jit, static_argnames=("m_bits", "k", "block_bt", "interpret"))
def bitalign_dc_batch(bases, succ_bits, patterns, p_lens, *, m_bits: int,
                      k: int, block_bt: int = DEFAULT_BT,
                      interpret: bool = False):
    """Batched BitAlign DC.

    bases: [B, N] int8; succ_bits: [B, N] uint32; patterns: [B, m_bits]
    int8 wildcard-padded; p_lens: [B] int32.
    Returns (dists [B, N] int32, R [B, N, k+1, nw] uint32).
    """
    nw = m_bits // WORD_BITS
    b, n = bases.shape
    bt = batch_tile(block_bt, b, interpret=interpret)
    node, pm, tail = _lane_inputs(bases, succ_bits, patterns, p_lens,
                                  m_bits=m_bits, k=k, bt=bt)
    bp = node.shape[-1]
    rows = (k + 1) * nw
    in_specs, scratch = _specs(n, nw, k, bt)
    kernel = functools.partial(_dc_kernel, n=n, k=k, nw=nw)
    dists, r = pl.pallas_call(
        kernel,
        grid=(bp // bt,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((n, bt), lambda i: (0, i)),
            pl.BlockSpec((n, rows, bt), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, bp), jnp.int32),
            jax.ShapeDtypeStruct((n, rows, bp), jnp.uint32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="bitalign_dc",
    )(node, pm, tail)
    r = r.reshape(n, k + 1, nw, bp).transpose(3, 0, 1, 2)
    return dists.T[:b], r[:b]


@functools.partial(
    jax.jit,
    static_argnames=("m_bits", "k", "span", "block_bt", "interpret"))
def bitalign_filter_batch(bases, succ_bits, patterns, p_lens, *, m_bits: int,
                          k: int, span: int, block_bt: int = DEFAULT_BT,
                          interpret: bool = False):
    """Distances-only BitAlign filter over ``[B, N]`` subgraph windows.

    Returns ``(d [B], off [B])`` int32: the minimum per-node distance
    over nodes ``[0, span)`` (``k+1`` when none matches) and the first
    node attaining it — ``min``/``argmin`` of `bitalign_dc_batch`'s
    ``dists[:, :span]``, without writing any per-node output.
    """
    nw = m_bits // WORD_BITS
    b, n = bases.shape
    bt = batch_tile(block_bt, b, interpret=interpret)
    node, pm, tail = _lane_inputs(bases, succ_bits, patterns, p_lens,
                                  m_bits=m_bits, k=k, bt=bt)
    bp = node.shape[-1]
    in_specs, scratch = _specs(n, nw, k, bt)
    kernel = functools.partial(_filter_kernel, n=n, k=k, nw=nw, span=span)
    best, off = pl.pallas_call(
        kernel,
        grid=(bp // bt,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, bt), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, bp), jnp.int32)] * 2,
        scratch_shapes=scratch,
        interpret=interpret,
        name="bitalign_filter",
    )(node, pm, tail)
    return best[0, :b], off[0, :b]
