"""Pallas TPU kernel for batched Myers bit-parallel edit distance.

Same lane strategy as the GenASM-DC kernel: one alignment per VPU lane,
word-major ``[nw, BT]`` bitvectors, sequential over text characters with
Pv/Mv/score carried in registers through a ``fori_loop``.  The multi-word
carry of Myers' additive term is a static unroll over ``nw`` words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.bitvector import NUM_CHARS, WORD_BITS


def _peq_table(pattern_tile: jnp.ndarray, nw: int) -> jnp.ndarray:
    """[5, nw, BT]: bit j of PEq[c] = 1 iff pattern[j] == c (LSB = pattern[0])."""
    p = pattern_tile.astype(jnp.int32)  # [BT, m_bits]
    weights = jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=jnp.uint32)
    out = []
    for c in range(NUM_CHARS):
        m = ((p == c) | (p == 4)).astype(jnp.uint32).reshape(p.shape[0], nw, WORD_BITS)
        out.append(jnp.sum(m * weights[None, None, :], axis=-1, dtype=jnp.uint32).T)
    return jnp.stack(out)


def _add_carry_wm(a: jnp.ndarray, b: jnp.ndarray, nw: int) -> jnp.ndarray:
    """Multi-word add on [nw, BT] word-major vectors (drop final carry)."""
    outs = []
    cin = jnp.zeros(a.shape[-1:], jnp.uint32)
    for wd in range(nw):
        s1 = a[wd] + b[wd]
        c1 = (s1 < a[wd]).astype(jnp.uint32)
        s2 = s1 + cin
        c2 = (s2 < s1).astype(jnp.uint32)
        outs.append(s2)
        cin = c1 | c2
    return jnp.stack(outs)


def _shl1_in_wm(x: jnp.ndarray, bit_in: jnp.ndarray) -> jnp.ndarray:
    carry = x >> 31
    shifted = x << 1
    incoming = jnp.concatenate([bit_in[None, :], carry[:-1]], axis=0)
    return shifted | incoming


def _myers_kernel(text_ref, pattern_ref, mlen_ref, dist_ref, *, n: int, nw: int,
                  mode: str):
    bt = text_ref.shape[0]
    peq = _peq_table(pattern_ref[...], nw)  # [5, nw, BT]
    m_len = mlen_ref[...].astype(jnp.int32)  # [BT]
    score_word = (m_len - 1) // WORD_BITS  # [BT]
    score_off = ((m_len - 1) % WORD_BITS).astype(jnp.uint32)
    cin = (
        jnp.ones((bt,), jnp.uint32) if mode == "global" else jnp.zeros((bt,), jnp.uint32)
    )

    def pick_word(v, wsel):
        out = jnp.zeros((bt,), jnp.uint32)
        for wd in range(nw):
            out = jnp.where(wsel == wd, v[wd], out)
        return out

    def step(j, state):
        Pv, Mv, score, best = state
        c = text_ref[:, j].astype(jnp.int32)
        Eq = jnp.zeros((nw, bt), jnp.uint32)
        for ch in range(NUM_CHARS):
            Eq = jnp.where((c == ch)[None, :], peq[ch], Eq)
        Xv = Eq | Mv
        Xh = (_add_carry_wm(Eq & Pv, Pv, nw) ^ Pv) | Eq
        Ph = Mv | ~(Xh | Pv)
        Mh = Pv & Xh
        ph_bit = (pick_word(Ph, score_word) >> score_off) & 1
        mh_bit = (pick_word(Mh, score_word) >> score_off) & 1
        score = score + ph_bit.astype(jnp.int32) - mh_bit.astype(jnp.int32)
        Ph = _shl1_in_wm(Ph, cin)
        Mh = _shl1_in_wm(Mh, jnp.zeros((bt,), jnp.uint32))
        Pv = Mh | ~(Xv | Ph)
        Mv = Ph & Xv
        best = jnp.minimum(best, score)
        return Pv, Mv, score, best

    Pv0 = jnp.full((nw, bt), 0xFFFFFFFF, jnp.uint32)
    Mv0 = jnp.zeros((nw, bt), jnp.uint32)
    Pv, Mv, score, best = lax.fori_loop(0, n, step, (Pv0, Mv0, m_len, m_len))
    dist_ref[...] = score if mode == "global" else best


@functools.partial(jax.jit, static_argnames=("m_bits", "mode", "block_bt", "interpret"))
def myers_distance_batch(
    texts: jnp.ndarray,
    patterns: jnp.ndarray,
    m_lens: jnp.ndarray,
    *,
    m_bits: int,
    mode: str = "global",
    block_bt: int = 128,
    interpret: bool = False,
):
    """Batched Myers distance via Pallas.

    ``texts``: [B, n] int8; ``patterns``: [B, m_bits] int8 wildcard-padded;
    ``m_lens``: [B] int32.  Returns [B] int32 distances (global NW or
    semiglobal min-over-prefixes per ``mode``).
    """
    nw = m_bits // WORD_BITS
    b, n = texts.shape
    if b % block_bt != 0:
        raise ValueError(f"batch {b} not a multiple of block_bt {block_bt}")
    kernel = functools.partial(_myers_kernel, n=n, nw=nw, mode=mode)
    return pl.pallas_call(
        kernel,
        grid=(b // block_bt,),
        in_specs=[
            pl.BlockSpec((block_bt, n), lambda i: (i, 0)),
            pl.BlockSpec((block_bt, m_bits), lambda i: (i, 0)),
            pl.BlockSpec((block_bt,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_bt,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
        interpret=interpret,
        name="myers",
    )(texts, patterns, m_lens)
