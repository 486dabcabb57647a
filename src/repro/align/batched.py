"""Batched windowed alignment on the Pallas GenASM-DC kernels.

`repro.core.genasm.align` runs the paper's window loop per alignment and
vmaps the whole thing — fine for the pure-`lax` DC, but it would drive
the Pallas kernels at batch 1 per window.  Here the loop nesting is
inverted: the *batch* advances through its window steps together, so
each step issues **one** kernel launch over `[B, w]` windows (the
lane-per-alignment mapping of DESIGN.md §2) and the data-dependent
traceback (`window_tb`/`window_tb_r`) vmaps over the kernel's TB store.
Lanes that finish early keep issuing no-op windows (advance 0) until the
scan ends — shapes stay static, which is what lets the serve engine
cache one executor per bucket.

The per-window commit rules are shared with `core/genasm.align` (one
`window_commit` helper), so the emitted distances and CIGARs are
bit-identical to the `lax` backend (the conformance suite and the golden
PAF test both pin this).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.bitvector import pattern_bitmasks
from repro.core.genasm import (AlignResult, GenASMConfig, pad_pattern,
                               pad_text, window_commit)
from repro.core.genasm_tb import OP_PAD, window_tb, window_tb_r
from repro.kernels.genasm_dc import window_dc_batch
from repro.kernels.genasm_dc_v2 import window_dc_batch_v2


@partial(jax.jit, static_argnames=("cfg", "p_cap", "emit_cigar", "store_r",
                                   "block_bt", "interpret"))
def batched_kernel_align(
    texts: jnp.ndarray,
    patterns: jnp.ndarray,
    p_lens: jnp.ndarray,
    t_lens: jnp.ndarray,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int | None = None,
    emit_cigar: bool = True,
    store_r: bool = False,
    block_bt: int = 128,
    interpret: bool = True,
) -> AlignResult:
    """Windowed GenASM over a batch, DC on the Pallas kernel.

    ``texts``/``patterns``: [B, *] int8 buffers; ``p_lens``/``t_lens``:
    [B] lengths.  ``store_r`` selects the v2 (R-only TB store) kernel.
    Returns a batched :class:`AlignResult`.
    """
    if p_cap is None:
        p_cap = int(patterns.shape[-1])
    n_win = cfg.n_windows(p_cap)
    max_steps = 2 * cfg.commit
    w, o, k = cfg.w, cfg.o, cfg.k
    b = texts.shape[0]
    p_lens = p_lens.astype(jnp.int32)
    t_lens = t_lens.astype(jnp.int32)

    pats = jax.vmap(lambda p, pl: pad_pattern(p, pl, p_cap, cfg))(
        patterns, p_lens)
    txts = jax.vmap(
        lambda t, tl: pad_text(t, tl, p_cap + n_win * cfg.commit, cfg))(
        texts, t_lens)

    dc = window_dc_batch_v2 if store_r else window_dc_batch
    slice_w = jax.vmap(lambda buf, i: lax.dynamic_slice(buf, (i,), (w,)))
    if store_r:
        tb_fn = jax.vmap(
            partial(window_tb_r, w=w, o=o, k=k, affine=cfg.affine))
    else:
        tb_fn = jax.vmap(partial(window_tb, w=w, o=o, k=k, affine=cfg.affine))

    def window_step(carry, _):
        cur_p, cur_t = carry[0], carry[1]  # each [B]
        sub_p = slice_w(pats, cur_p)  # [B, w]
        sub_t = slice_w(txts, cur_t)
        with jax.named_scope("dc_window"):
            d_min, tb = dc(sub_t, sub_p, w=w, k=k, block_bt=block_bt,
                           interpret=interpret)
        cap_p = jnp.minimum(jnp.int32(cfg.commit), p_lens - cur_p)
        with jax.named_scope("traceback"):
            if store_r:
                pm = jax.vmap(lambda p: pattern_bitmasks(p, w))(sub_p)
                pc, tc, err, ops, n_ops, stuck = tb_fn(
                    tb, sub_t, pm, jnp.minimum(d_min, k), cap_p)
            else:
                pc, tc, err, ops, n_ops, stuck = tb_fn(
                    tb, jnp.minimum(d_min, k), cap_p)
        new_carry, n_emit = window_commit(
            carry, d_min=d_min, pc=pc, tc=tc, err=err, n_ops=n_ops,
            stuck=stuck, p_len=p_lens, k=k)
        return new_carry, (ops, n_emit)

    zeros = jnp.zeros((b,), jnp.int32)
    init = (zeros, zeros, zeros, jnp.zeros((b,), bool), p_lens <= 0)
    (fin_p, fin_t, dist, failed, done), (ops_w, n_ops_w) = lax.scan(
        window_step, init, None, length=n_win)
    failed = failed | (~done)
    # scan stacked per-window outputs: ops_w [n_win, B, max_steps]
    ops_w = jnp.swapaxes(ops_w, 0, 1)  # [B, n_win, max_steps]
    n_ops_w = jnp.swapaxes(n_ops_w, 0, 1)  # [B, n_win]

    cap = n_win * max_steps
    if emit_cigar:
        def scatter(ops_b, n_b):
            offsets = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(n_b)[:-1]])
            step_idx = jnp.arange(max_steps)[None, :]
            valid = step_idx < n_b[:, None]
            pos = jnp.where(valid, offsets[:, None] + step_idx, cap)
            out = jnp.full((cap,), OP_PAD, jnp.int8)
            return out.at[pos.reshape(-1)].set(ops_b.reshape(-1), mode="drop")

        out = jax.vmap(scatter)(ops_w, n_ops_w)
    else:
        out = jnp.full((b, 1), OP_PAD, jnp.int8)
    n_total = jnp.sum(n_ops_w, axis=-1)

    return AlignResult(
        distance=jnp.where(failed, jnp.int32(-1), dist),
        ops=out,
        n_ops=n_total,
        text_consumed=fin_t,
        failed=failed,
    )
