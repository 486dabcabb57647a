"""GenASM: chained divide-and-conquer alignment (DC + TB per window).

This is the paper's full read-alignment dataflow (Figure 4-3): the text
region and query pattern are cut into overlapping windows (W=64, O=24 by
default); per window GenASM-DC generates the intermediate bitvectors and
GenASM-TB commits up to ``W-O`` characters of traceback; windows repeat
until the pattern is consumed.  Everything is shape-static so the whole
aligner vmaps over batches of (candidate text region, read) pairs and
pjit/shard_maps over the data axes.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .bitvector import SENTINEL, WILDCARD
from .genasm_tb import OP_PAD, window_tb
from . import genasm_dc


class GenASMConfig(NamedTuple):
    """Window geometry (paper defaults W=64, O=24, k_window=O).

    Backend selection (pure-lax vs the Pallas kernels) is *not* part of
    this config — it belongs to `repro.align`'s dispatch layer, which
    keys its executor/autotune caches on the backend name separately.
    """

    w: int = 64
    o: int = 24
    k: int = 24
    affine: bool = True
    store_r: bool = False  # v2 TB store: R rows only (3× less TB traffic)

    @property
    def commit(self) -> int:
        return self.w - self.o

    def n_windows(self, max_pattern_len: int) -> int:
        return -(-max_pattern_len // self.commit) + 2

    def ops_cap(self, p_cap: int) -> int:
        """CIGAR ops/path buffer width every backend emits at ``p_cap``.

        Each of the ``n_windows`` steps commits at most ``2·commit`` ops
        (all-insertion worst case).  Shared by the align backends and the
        graph mapper's zero-survivor short-circuit, whose canned result
        must be shaped exactly like a real align launch's.
        """
        return self.n_windows(p_cap) * 2 * self.commit


class AlignResult(NamedTuple):
    distance: jnp.ndarray  # int32 total edit distance (approx. per paper)
    ops: jnp.ndarray  # [cap] int8 packed CIGAR (-1 padded)
    n_ops: jnp.ndarray  # int32
    text_consumed: jnp.ndarray  # int32
    failed: jnp.ndarray  # bool — a window had no alignment within k
    # graph backends only: [cap] int32 window-relative node offset consumed
    # by each op (-1 for I/padding); None for the linear backends
    nodes: jnp.ndarray | None = None


def pad_pattern(pattern: jnp.ndarray, p_len, cap: int, cfg: GenASMConfig):
    """Pad/trim a pattern buffer to ``cap + w`` with wildcards after ``p_len``."""
    size = cap + cfg.w
    buf = jnp.full((size,), WILDCARD, jnp.int8)
    buf = lax.dynamic_update_slice(buf, pattern.astype(jnp.int8)[: size], (0,))
    idx = jnp.arange(size)
    return jnp.where(idx < p_len, buf, WILDCARD).astype(jnp.int8)


def pad_text(text: jnp.ndarray, t_len, cap: int, cfg: GenASMConfig):
    """Pad/trim a text buffer to ``cap + w`` with sentinels after ``t_len``."""
    size = cap + cfg.w
    buf = jnp.full((size,), SENTINEL, jnp.int8)
    buf = lax.dynamic_update_slice(buf, text.astype(jnp.int8)[: size], (0,))
    idx = jnp.arange(size)
    return jnp.where(idx < t_len, buf, SENTINEL).astype(jnp.int8)


def window_commit(carry, *, d_min, pc, tc, err, n_ops, stuck, p_len, k):
    """Advance the window-scan carry by one DC+TB window's outcome.

    The single source of the commit rules (fail/stall masking, advance
    gating, completion): both the per-alignment scan here and the
    batched kernel driver in `repro.align.batched` call this, which is
    what makes their outputs bit-identical.  All operands may be scalars
    (per-lane under vmap) or ``[B]`` vectors — the logic broadcasts.

    Returns ``(new_carry, n_emit)`` where ``n_emit`` is the number of
    CIGAR ops this window actually contributes (0 for done/failed lanes).
    """
    cur_p, cur_t, dist, failed, done = carry
    win_fail = d_min > k
    this_fail = (win_fail | stuck) & (~done)
    adv_p = jnp.where(done | this_fail, 0, pc)
    adv_t = jnp.where(done | this_fail, 0, tc)
    n_emit = jnp.where(done | this_fail, 0, n_ops)
    dist = dist + jnp.where(done | this_fail, 0, err)
    new_done = done | this_fail | (cur_p + adv_p >= p_len)
    return (cur_p + adv_p, cur_t + adv_t, dist, failed | this_fail,
            new_done), n_emit


@partial(jax.jit, static_argnames=("cfg", "p_cap", "emit_cigar"))
def align(
    text: jnp.ndarray,
    pattern: jnp.ndarray,
    p_len: jnp.ndarray,
    t_len: jnp.ndarray,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int | None = None,
    emit_cigar: bool = True,
) -> AlignResult:
    """Align ``pattern[:p_len]`` against ``text[:t_len]`` anchored at text[0].

    ``text``/``pattern`` are fixed-size int8 buffers (contents past the
    lengths are ignored).  Semi-global: the pattern must be fully consumed,
    trailing text is free.  Vmap over leading axes for batches.
    """
    if p_cap is None:
        p_cap = int(pattern.shape[-1])
    n_win = cfg.n_windows(p_cap)
    max_steps = 2 * cfg.commit
    w, o, k = cfg.w, cfg.o, cfg.k

    pat = pad_pattern(pattern, p_len, p_cap, cfg)
    txt = pad_text(text, t_len, p_cap + n_win * cfg.commit, cfg)

    if cfg.store_r:
        dc_fn = lambda st, sp: genasm_dc.window_dc_r(st, sp, w=w, k=k)
    else:
        dc_fn = lambda st, sp: genasm_dc.window_dc(st, sp, w=w, k=k)

    def window_step(carry, _):
        cur_p, cur_t = carry[0], carry[1]
        sub_p = lax.dynamic_slice(pat, (cur_p,), (w,))
        sub_t = lax.dynamic_slice(txt, (cur_t,), (w,))
        # the same stage scopes as the Pallas path (align/batched.py)
        with jax.named_scope("dc_window"):
            d_min, tb = dc_fn(sub_t, sub_p)
        cap_p = jnp.minimum(jnp.int32(cfg.commit), p_len - cur_p)
        with jax.named_scope("traceback"):
            if cfg.store_r:
                from .bitvector import pattern_bitmasks
                from .genasm_tb import window_tb_r

                pm = pattern_bitmasks(sub_p, w)
                pc, tc, err, ops, n_ops, stuck = window_tb_r(
                    tb, sub_t, pm, jnp.minimum(d_min, k), cap_p, w=w, o=o,
                    k=k, affine=cfg.affine)
            else:
                pc, tc, err, ops, n_ops, stuck = window_tb(
                    tb, jnp.minimum(d_min, k), cap_p, w=w, o=o, k=k,
                    affine=cfg.affine)
        new_carry, n_emit = window_commit(
            carry, d_min=d_min, pc=pc, tc=tc, err=err, n_ops=n_ops,
            stuck=stuck, p_len=p_len, k=k)
        return new_carry, (ops, n_emit)

    init = (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.asarray(False), p_len <= 0)
    (fin_p, fin_t, dist, failed, done), (ops_w, n_ops_w) = lax.scan(
        window_step, init, None, length=n_win
    )
    failed = failed | (~done)

    if emit_cigar:
        cap = n_win * max_steps
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(n_ops_w)[:-1]])
        step_idx = jnp.arange(max_steps)[None, :]
        valid = step_idx < n_ops_w[:, None]
        pos = jnp.where(valid, offsets[:, None] + step_idx, cap)
        out = jnp.full((cap,), OP_PAD, jnp.int8)
        out = out.at[pos.reshape(-1)].set(ops_w.reshape(-1), mode="drop")
        n_total = jnp.sum(n_ops_w)
    else:
        out = jnp.full((1,), OP_PAD, jnp.int8)
        n_total = jnp.sum(n_ops_w)

    return AlignResult(
        distance=jnp.where(failed, jnp.int32(-1), dist),
        ops=out,
        n_ops=n_total,
        text_consumed=fin_t,
        failed=failed,
    )


def align_batch(texts, patterns, p_lens, t_lens, *, cfg=GenASMConfig(), emit_cigar=True):
    """vmap'd :func:`align` over a batch of pairs."""
    f = partial(align, cfg=cfg, emit_cigar=emit_cigar)
    return jax.vmap(f)(texts, patterns, p_lens, t_lens)
