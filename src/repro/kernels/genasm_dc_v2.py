"""GenASM-DC kernel v2 — beyond-paper TB-store compression (§Perf #3).

Hypothesis (napkin math): the paper's accelerator streams 3 intermediate
bitvectors (M, I, D) per (i, d) cell to TB-SRAM — 24 B/cycle/PE; but all
four TB checks are *derivable from the status bitvectors alone*:

    D(i,d) = R(i+1, d-1)           S(i,d) = shl1(D) = shl1(R(i+1, d-1))
    I(i,d) = shl1(R(i, d-1))       M(i,d) = shl1(R(i+1, d)) | PM[text[i]]

so storing only ``R`` rows ([W+1, k+1, nw] incl. the i=W boundary = all
ones) cuts TB-store writes and footprint by 3× (38.4 KB → 13 KB per
window at k=24), at the cost of one extra indexed read (the i+1 row) and
a PM re-derivation per TB step — TB executes ≤ W−O steps/window vs the
DC's W·(k+1) writes, so trading DC-side bytes for TB-side gathers is a
clear win (DC is the streaming bottleneck the paper engineered TB-SRAMs
for).  Confirmed by measurement in EXPERIMENTS.md §Perf.

Same lane-major layout as `genasm_dc` (batch on the last axis; store row
``d·nw + j`` = word ``j`` of status row ``d``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.bitvector import NUM_CHARS, WORD_BITS

from .genasm_dc import (DEFAULT_BT, _ONES, batch_tile, dc_rows,
                        load_pm, min_distance, pm_lanes,
                        select_pm, to_lanes)


def _dc_kernel_v2(text_ref, pm_ref, dmin_ref, r_ref, *, w: int, k: int,
                  nw: int):
    bt = text_ref.shape[-1]
    pm = load_pm(pm_ref, nw)
    ones = jnp.full((1, bt), _ONES, jnp.uint32)
    r_ref[w] = jnp.full(r_ref.shape[1:], _ONES, jnp.uint32)  # row i = w

    def step(s, flat):
        i = w - 1 - s
        R_old = [list(flat[d * nw:(d + 1) * nw]) for d in range(k + 1)]
        R_new, _ = dc_rows(R_old, select_pm(pm, text_ref[pl.ds(i, 1), :]), k)
        for d in range(k + 1):
            for j in range(nw):
                r_ref[i, pl.ds(d * nw + j, 1), :] = R_new[d][j]
        return tuple(x for row in R_new for x in row)

    fin = lax.fori_loop(0, w, step, (ones,) * ((k + 1) * nw))
    dmin_ref[...] = min_distance(
        [fin[d * nw:(d + 1) * nw] for d in range(k + 1)], k)


@functools.partial(jax.jit, static_argnames=("w", "k", "block_bt", "interpret"))
def window_dc_batch_v2(sub_texts, sub_patterns, *, w: int = 64, k: int = 24,
                       block_bt: int = DEFAULT_BT, interpret: bool = False):
    """Returns ``(d_min [B], R [B, w+1, k+1, nw])`` — status rows only."""
    nw = w // WORD_BITS
    b = sub_texts.shape[0]
    bt = batch_tile(block_bt, b, interpret=interpret)
    text = to_lanes(sub_texts.astype(jnp.int32), bt, 4)  # [w, B']
    pm = pm_lanes(sub_patterns, w, bt)
    bp = text.shape[-1]
    rows = (k + 1) * nw
    kernel = functools.partial(_dc_kernel_v2, w=w, k=k, nw=nw)
    d, r = pl.pallas_call(
        kernel,
        grid=(bp // bt,),
        in_specs=[
            pl.BlockSpec((w, bt), lambda i: (0, i)),
            pl.BlockSpec((NUM_CHARS * nw, bt), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt), lambda i: (0, i)),
            pl.BlockSpec((w + 1, rows, bt), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, bp), jnp.int32),
            jax.ShapeDtypeStruct((w + 1, rows, bp), jnp.uint32),
        ],
        interpret=interpret,
        name="genasm_dc_v2",
    )(text, pm)
    r = r.reshape(w + 1, k + 1, nw, bp).transpose(3, 0, 1, 2)
    return d[0, :b], r[:b]
