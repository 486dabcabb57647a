"""Pallas TPU kernel for GenASM-DC window batches.

TPU adaptation of the paper's 64-PE bit-parallel DC systolic array
(DESIGN.md §2): instead of unrolling the (i, d) anti-diagonals across PEs,
the batch of independent window alignments is the vector axis — every VPU
lane advances one alignment, sequentially in ``i`` (text chars) and with a
*statically unrolled* ``d`` loop (the k+1 distance rows, k=24 default).

Data layout inside the kernel is **lane-major**: every array carries the
batch tile ``BT`` (a multiple of 128 when compiled) on its last axis, so
one bitvector word of one distance row is a ``[1, BT]`` vector and a
text column is one ``[1, BT]`` row.  Stores are ``[.., rows, BT]`` with
``rows = (k+1)·nw`` (v2) or ``(k+1)·3·nw`` (v1, M/I/D per row): row
``(d·3 + t)·nw + j`` holds word ``j`` of vector ``t`` at distance ``d``.
The PM table is built outside the kernel (XLA, `core.bitvector`) and
enters as ``[NUM_CHARS·nw, BT]``; the wrappers transpose the stores back
to the batch-major layout the traceback reads.

VMEM per block (BT=128, W=64, k=24): v1 TB store 4.9 MB, v2 R store
1.7 MB, text + PM 40 KB — double-buffered, inside v5e's 16 MiB default
scoped limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.bitvector import NUM_CHARS, WORD_BITS, pattern_bitmasks

DEFAULT_BT = 128
LANES = 128  # TPU vector lane width: compiled batch tiles are multiples
_ONES = 0xFFFFFFFF


def batch_tile(block_bt: int | None, batch: int, *, interpret: bool) -> int:
    """Batch tile of one kernel launch.

    ``min(block_bt, max(8, batch))`` — small tiles keep interpret-mode
    CPU runs cheap — rounded up to whole 128-lane vectors when the
    kernel is compiled, the only block widths Mosaic accepts.
    """
    bt = min(block_bt or DEFAULT_BT, max(8, batch))
    if interpret:
        return bt
    return -(-bt // LANES) * LANES


def to_lanes(x: jnp.ndarray, bt: int, fill) -> jnp.ndarray:
    """``[B, ...]`` → ``[..., B']``: batch moved to the last (lane) axis and
    padded with ``fill`` up to a multiple of ``bt``."""
    b = x.shape[0]
    x = jnp.moveaxis(x, 0, -1)
    pad = (-b) % bt
    if pad:
        x = jnp.concatenate(
            [x, jnp.full(x.shape[:-1] + (pad,), fill, x.dtype)], axis=-1)
    return x


def pm_lanes(patterns: jnp.ndarray, m_bits: int, bt: int) -> jnp.ndarray:
    """``[B, m_bits]`` patterns → lane-major PM table ``[NUM_CHARS·nw, B']``
    (row ``c·nw + j`` = word ``j`` of char ``c``'s mask; padded lanes are
    all-wildcard patterns)."""
    pm = pattern_bitmasks(patterns, m_bits)  # [B, NUM_CHARS, nw]
    return to_lanes(pm.reshape(pm.shape[0], -1), bt, 0)


def shl1_words(x: list) -> list:
    """Shift-left-1 of a little-endian word list (each ``[1, BT]``)."""
    return [x[0] << 1] + [(x[j] << 1) | (x[j - 1] >> 31)
                          for j in range(1, len(x))]


def load_pm(pm_ref, nw: int) -> list:
    """PM table rows as ``pm[c][j]`` ``[1, BT]`` vectors."""
    return [[pm_ref[pl.ds(c * nw + j, 1), :] for j in range(nw)]
            for c in range(NUM_CHARS)]


def select_pm(pm: list, c: jnp.ndarray) -> list:
    """Per-lane PM words of text char ``c`` (``[1, BT]`` int32; chars
    outside the alphabet select all-zero words)."""
    out = []
    for j in range(len(pm[0])):
        cur = jnp.zeros(c.shape, jnp.uint32)
        for ch in range(NUM_CHARS):
            cur = jnp.where(c == ch, pm[ch][j], cur)
        out.append(cur)
    return out


def dc_rows(comb: list, cur_pm: list, k: int, mask=None):
    """One text step of the DC recurrence on word lists.

    ``comb[d]`` is the previous text char's status row ``d`` (its word
    list), ``mask`` an optional per-word AND mask (BitAlign's pattern
    tail).  Returns ``(R_new, mid)`` with ``mid[d] = (M, I, D)`` word
    lists for ``d ≥ 1`` (the v1 TB store).
    """
    def m(words):
        return words if mask is None else [a & t for a, t in zip(words, mask)]

    rows = [m([a | p for a, p in zip(shl1_words(comb[0]), cur_pm)])]
    mid = [None]
    for d in range(1, k + 1):
        D = comb[d - 1]
        S = shl1_words(comb[d - 1])
        I = shl1_words(rows[d - 1])
        M = [a | p for a, p in zip(shl1_words(comb[d]), cur_pm)]
        rows.append(m([dd & s & ii & mm for dd, s, ii, mm in zip(D, S, I, M)]))
        mid.append((M, I, D))
    return rows, mid


def min_distance(rows: list, k: int) -> jnp.ndarray:
    """Smallest ``d`` whose status row has MSB 0 (``k+1`` if none)."""
    out = jnp.full(rows[0][0].shape, k + 1, jnp.int32)
    for d in range(k, -1, -1):
        out = jnp.where((rows[d][-1] >> 31) == 0, jnp.int32(d), out)
    return out


def _dc_kernel(text_ref, pm_ref, dmin_ref, tb_ref, *, w: int, k: int,
               nw: int):
    bt = text_ref.shape[-1]
    pm = load_pm(pm_ref, nw)
    ones = jnp.full((1, bt), _ONES, jnp.uint32)

    def step(s, flat):
        i = w - 1 - s  # text position, scanned w-1 .. 0
        R_old = [list(flat[d * nw:(d + 1) * nw]) for d in range(k + 1)]
        cur_pm = select_pm(pm, text_ref[pl.ds(i, 1), :])
        R_new, mid = dc_rows(R_old, cur_pm, k)
        mid[0] = (R_new[0], [ones] * nw, [ones] * nw)  # d=0: (R0, 1s, 1s)
        for d in range(k + 1):
            for t in range(3):
                for j in range(nw):
                    tb_ref[i, pl.ds((d * 3 + t) * nw + j, 1), :] = mid[d][t][j]
        return tuple(x for row in R_new for x in row)

    fin = lax.fori_loop(0, w, step, (ones,) * ((k + 1) * nw))
    rows = [fin[d * nw:(d + 1) * nw] for d in range(k + 1)]
    dmin_ref[...] = min_distance(rows, k)


@functools.partial(
    jax.jit, static_argnames=("w", "k", "block_bt", "interpret")
)
def window_dc_batch(
    sub_texts: jnp.ndarray,
    sub_patterns: jnp.ndarray,
    *,
    w: int = 64,
    k: int = 24,
    block_bt: int = DEFAULT_BT,
    interpret: bool = False,
):
    """Batched GenASM-DC windows via Pallas.

    ``sub_texts``/``sub_patterns``: [B, w] int8 (any B: the batch is
    padded with sentinel windows up to the `batch_tile`).  Returns
    ``(d_min [B] int32, tb [B, w, k+1, 3, nw] uint32)`` identical to
    vmapped :func:`repro.core.genasm_dc.window_dc`.
    """
    if w % WORD_BITS != 0:
        raise ValueError("w must be a multiple of 32")
    nw = w // WORD_BITS
    b = sub_texts.shape[0]
    bt = batch_tile(block_bt, b, interpret=interpret)
    text = to_lanes(sub_texts.astype(jnp.int32), bt, 4)  # [w, B']
    pm = pm_lanes(sub_patterns, w, bt)  # [5·nw, B']
    bp = text.shape[-1]
    rows = (k + 1) * 3 * nw

    kernel = functools.partial(_dc_kernel, w=w, k=k, nw=nw)
    d, tb = pl.pallas_call(
        kernel,
        grid=(bp // bt,),
        in_specs=[
            pl.BlockSpec((w, bt), lambda i: (0, i)),
            pl.BlockSpec((NUM_CHARS * nw, bt), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt), lambda i: (0, i)),
            pl.BlockSpec((w, rows, bt), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, bp), jnp.int32),
            jax.ShapeDtypeStruct((w, rows, bp), jnp.uint32),
        ],
        interpret=interpret,
        name="genasm_dc",
    )(text, pm)
    tb = tb.reshape(w, k + 1, 3, nw, bp).transpose(4, 0, 1, 2, 3)
    return d[0, :b], tb[:b]
