"""The sequence-to-graph alignment backends (registered on import).

Two entries in the `repro.align` registry, sharing the uniform dispatch
signature:

  * ``graph_lax``    — `windowed.graph_align` vmapped (pure-`lax` BitAlign
    DC + graph TB inside the shared window loop)
  * ``graph_pallas`` — batched window loop driving the Pallas BitAlign DC
    kernel (`repro.kernels.bitalign`): the batch advances through its
    window steps together, one ``[B, w]`` kernel launch per step, with
    the graph traceback vmapped over the kernel's R-only store — the
    same inverted-loop strategy as `repro.align.batched`.

``texts`` may be **packed graph text** (uint32, see `windowed`) or plain
int8 linear text — the latter is packed as a hop-0 chain, which is what
lets the linear conformance suite (and the ``REPRO_ALIGN_BACKEND``
matrix) drive the graph backends with unchanged inputs and expect
bit-identical results against ``lax``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.align.api import register_backend
from repro.core.bitvector import pattern_bitmasks
from repro.core.genasm import AlignResult, GenASMConfig, pad_pattern, \
    window_commit
from repro.core.genasm_tb import OP_PAD

from .windowed import (_graph_buf_cap, _scatter_windows, graph_align,
                       pack_linear_text, pad_graph_text, text_window,
                       window_tb_graph, window_view)


def as_graph_text(texts: jnp.ndarray) -> jnp.ndarray:
    """Accept packed graph text (uint32) or plain int8 text (chain-packed)."""
    texts = jnp.asarray(texts)
    if texts.dtype == jnp.uint32:
        return texts
    return pack_linear_text(texts)


def _graph_lax_fn(texts, patterns, p_lens, t_lens, *, cfg: GenASMConfig,
                  p_cap: int, emit_cigar: bool, block_bt: int,
                  interpret: bool):
    del block_bt, interpret  # no kernel underneath
    f = partial(graph_align, cfg=cfg, p_cap=p_cap, emit_cigar=emit_cigar)
    return jax.vmap(f)(as_graph_text(texts), patterns,
                       jnp.asarray(p_lens, jnp.int32),
                       jnp.asarray(t_lens, jnp.int32))


@partial(jax.jit, static_argnames=("cfg", "p_cap", "emit_cigar", "block_bt",
                                   "interpret"))
def batched_graph_align(
    texts: jnp.ndarray,
    patterns: jnp.ndarray,
    p_lens: jnp.ndarray,
    t_lens: jnp.ndarray,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int | None = None,
    emit_cigar: bool = True,
    block_bt: int = 128,
    interpret: bool = True,
) -> AlignResult:
    """Windowed BitAlign over a batch, DC on the Pallas kernel."""
    from repro.kernels.bitalign import bitalign_dc_batch

    if p_cap is None:
        p_cap = int(patterns.shape[-1])
    n_win = cfg.n_windows(p_cap)
    max_steps = 2 * cfg.commit  # ops emitted per window; cap = cfg.ops_cap
    w, o, k = cfg.w, cfg.o, cfg.k
    b = texts.shape[0]
    p_lens = p_lens.astype(jnp.int32)
    t_lens = t_lens.astype(jnp.int32)

    gtexts = as_graph_text(texts)
    pats = jax.vmap(lambda p, pl: pad_pattern(p, pl, p_cap, cfg))(
        patterns, p_lens)
    gbufs = jax.vmap(
        lambda t, tl: pad_graph_text(t, tl, _graph_buf_cap(p_cap, cfg), cfg))(
        gtexts, t_lens)

    tw = text_window(cfg)
    slice_w = jax.vmap(lambda buf, i: lax.dynamic_slice(buf, (i,), (w,)))
    slice_t = jax.vmap(lambda buf, i: lax.dynamic_slice(buf, (i,), (tw,)))
    tb_fn = jax.vmap(
        partial(window_tb_graph, w=w, o=o, k=k, affine=cfg.affine))

    def window_step(carry, _):
        cur_p, cur_t = carry[0], carry[1]
        sub_p = slice_w(pats, cur_p)  # [B, w]
        sub_g = slice_t(gbufs, cur_t)
        bases, succ, p_win = window_view(sub_g, p_lens - cur_p, w)
        d_all, store = bitalign_dc_batch(
            bases, succ, sub_p, p_win, m_bits=w, k=k, block_bt=block_bt,
            interpret=interpret)  # store: [B, tw, k+1, nw]
        d_min = d_all[:, 0]  # anchored at window node 0
        cap_p = jnp.minimum(jnp.int32(cfg.commit), p_lens - cur_p)
        pm = jax.vmap(lambda p: pattern_bitmasks(p, w))(sub_p)
        pc, tc, err, ops, n_ops, nodes, stuck = tb_fn(
            store, succ, bases, pm, jnp.minimum(d_min, k), cap_p)
        new_carry, n_emit = window_commit(
            carry, d_min=d_min, pc=pc, tc=tc, err=err, n_ops=n_ops,
            stuck=stuck, p_len=p_lens, k=k)
        nodes = jnp.where(nodes >= 0, nodes + cur_t[:, None], -1)
        return new_carry, (ops, nodes, n_emit)

    zeros = jnp.zeros((b,), jnp.int32)
    init = (zeros, zeros, zeros, jnp.zeros((b,), bool), p_lens <= 0)
    (fin_p, fin_t, dist, failed, done), (ops_w, nodes_w, n_ops_w) = lax.scan(
        window_step, init, None, length=n_win)
    failed = failed | (~done)
    ops_w = jnp.swapaxes(ops_w, 0, 1)  # [B, n_win, max_steps]
    nodes_w = jnp.swapaxes(nodes_w, 0, 1)
    n_ops_w = jnp.swapaxes(n_ops_w, 0, 1)  # [B, n_win]

    cap = cfg.ops_cap(p_cap)
    if emit_cigar:
        out_ops = jax.vmap(
            lambda v, n: _scatter_windows(v, n, cap, OP_PAD, jnp.int8))(
            ops_w, n_ops_w)
        out_nodes = jax.vmap(
            lambda v, n: _scatter_windows(v, n, cap, -1, jnp.int32))(
            nodes_w, n_ops_w)
    else:
        out_ops = jnp.full((b, 1), OP_PAD, jnp.int8)
        out_nodes = None
    n_total = jnp.sum(n_ops_w, axis=-1)

    return AlignResult(
        distance=jnp.where(failed, jnp.int32(-1), dist),
        ops=out_ops,
        n_ops=n_total,
        text_consumed=fin_t,
        failed=failed,
        nodes=out_nodes,
    )


def _graph_pallas_fn(texts, patterns, p_lens, t_lens, *, cfg: GenASMConfig,
                     p_cap: int, emit_cigar: bool, block_bt: int,
                     interpret: bool):
    return batched_graph_align(
        texts, patterns, p_lens, t_lens, cfg=cfg, p_cap=p_cap,
        emit_cigar=emit_cigar, block_bt=block_bt, interpret=interpret)


register_backend(
    "graph_lax", _graph_lax_fn,
    description="pure-jax.lax windowed BitAlign (sequence-to-graph; accepts "
                "packed graph text or plain int8 text as a chain)")
register_backend(
    "graph_pallas", _graph_pallas_fn, uses_pallas=True,
    description="Pallas BitAlign DC kernel in the batched window loop "
                "(R-only TB store, graph traceback on host lanes)")
