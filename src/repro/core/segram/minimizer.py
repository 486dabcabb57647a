"""MinSeed: minimizer-based indexing & seeding (paper §6.1, §6.5, §6.6).

(w, k)-minimizers: in every window of ``w`` consecutive k-mers the one with
the smallest hash is sampled.  The reference index is a sorted
(hash, position) table built offline (the paper's pre-processing step);
queries are JAX ``searchsorted`` lookups, so seeding runs sharded on
device.  Frequency filtering discards the most frequent minimizers
(paper: top 0.02%), exactly like MinSeed's filter stage.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def kmer_codes(seq: jnp.ndarray, k: int) -> jnp.ndarray:
    """Packed 2-bit k-mer codes for every position (length n-k+1).

    Positions whose k-mer touches a non-ACGT char get code 0xFFFFFFFF
    (excluded from minimizers).
    """
    n = seq.shape[-1]
    idx = jnp.arange(n - k + 1)[:, None] + jnp.arange(k)[None, :]
    kmers = seq[idx].astype(jnp.uint32)  # [n-k+1, k]
    valid = jnp.all(kmers < 4, axis=-1)
    shifts = jnp.uint32(2) * jnp.arange(k - 1, -1, -1, dtype=jnp.uint32)
    code = jnp.sum((kmers & 3) << shifts, axis=-1, dtype=jnp.uint32)
    return jnp.where(valid, code, jnp.uint32(0xFFFFFFFF))


def hash32(x: jnp.ndarray) -> jnp.ndarray:
    """Invertible 32-bit mix (murmur3 finalizer) — the minimizer ordering."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


_INVALID = np.uint32(0xFFFFFFFF)
# bases per device pass of the index build: the per-position k-mer and
# window gathers cost ~100 B/base, so a whole chromosome is built in
# overlapping chunks instead of one program
BUILD_CHUNK = 1 << 22


def _kmer_hashes(seq: jnp.ndarray, k: int) -> jnp.ndarray:
    """Minimizer-order hash per k-mer (``0xFFFFFFFF`` for invalid ones)."""
    codes = kmer_codes(seq, k)
    return jnp.where(codes == _INVALID, _INVALID, hash32(codes))


def _window_argmin(h: jnp.ndarray, w: int) -> jnp.ndarray:
    """Position of the first minimal hash in every ``w``-window of ``h``."""
    n_win = h.shape[0] - w + 1
    widx = jnp.arange(n_win)[:, None] + jnp.arange(w)[None, :]
    return jnp.argmin(h[widx], axis=-1) + jnp.arange(n_win)


@partial(jax.jit, static_argnames=("w", "k"))
def minimizers(seq: jnp.ndarray, *, w: int, k: int):
    """Minimizer sampling (paper Figure 6-4).

    Returns ``(is_min [n-k+1] bool, hashes [n-k+1] uint32)``: positions that
    are the minimum-hash k-mer of at least one w-window.
    """
    h = _kmer_hashes(seq, k)
    arg = _window_argmin(h, w)
    is_min = jnp.zeros((h.shape[0],), bool).at[arg].set(True)
    is_min = is_min & (h != _INVALID)
    return is_min, h


@partial(jax.jit, static_argnames=("w", "k"))
def _chunk_minimizers(seq: jnp.ndarray, *, w: int, k: int):
    h = _kmer_hashes(seq, k)
    return _window_argmin(h, w), h


def host_minimizers(ref: np.ndarray, *, w: int, k: int,
                    chunk: int = BUILD_CHUNK):
    """`minimizers` of a whole reference as host arrays, built on the
    device ``chunk`` windows at a time (each chunk reads ``k-1 + w-1``
    bases past its end), so device memory stays bounded for any
    reference length.  Bitwise equal to one `minimizers` pass."""
    n = len(ref)
    if n <= chunk:
        is_min, h = minimizers(jnp.asarray(ref), w=w, k=k)
        return np.asarray(is_min), np.asarray(h)
    n_k = n - k + 1
    n_win = n_k - w + 1
    span = chunk + (k - 1) + (w - 1)
    is_min = np.zeros(n_k, bool)
    h = np.empty(n_k, np.uint32)
    for s in range(0, n_win, chunk):
        seg = np.full(span, 4, np.int8)  # sentinel pad: invalid k-mers
        part = ref[s:s + span]
        seg[:len(part)] = part
        arg, hh = _chunk_minimizers(jnp.asarray(seg), w=w, k=k)
        m = min(chunk, n_win - s)
        is_min[s + np.asarray(arg[:m])] = True
        e = min(s + chunk + w - 1, n_k)
        h[s:e] = np.asarray(hh[:e - s])
    return is_min & (h != _INVALID), h


class MinimizerIndex(NamedTuple):
    """Sorted minimizer table (host-built, device-queryable)."""

    hashes: np.ndarray  # [M] uint32 sorted
    positions: np.ndarray  # [M] int32 reference positions
    freq_cap: int


def build_index(ref: np.ndarray, *, w: int = 10, k: int = 15,
                freq_frac: float = 0.0002) -> MinimizerIndex:
    """Offline index construction (paper §6.5) with frequency filtering."""
    is_min, h = host_minimizers(np.asarray(ref, np.int8), w=w, k=k)
    pos = np.nonzero(is_min)[0].astype(np.int32)
    hh = h[pos]
    order = np.argsort(hh, kind="stable")
    hh, pos = hh[order], pos[order]
    # frequency filter: drop hashes occurring more than cap times
    uniq, counts = np.unique(hh, return_counts=True)
    if len(uniq):
        cap = max(1, int(np.quantile(counts, 1.0 - freq_frac)))
        bad = uniq[counts > cap]
        keep = ~np.isin(hh, bad)
        hh, pos = hh[keep], pos[keep]
    else:
        cap = 1
    return MinimizerIndex(hashes=hh, positions=pos, freq_cap=cap)


@partial(jax.jit, static_argnames=("w", "k", "max_seeds", "max_candidates"))
def seed_candidates(
    read: jnp.ndarray,
    idx_hashes: jnp.ndarray,
    idx_positions: jnp.ndarray,
    *,
    w: int = 10,
    k: int = 15,
    max_seeds: int = 64,
    max_candidates: int = 8,
):
    """MinSeed query: read minimizers → candidate mapping locations.

    Candidate region start = ref_pos − read_pos (paper Figure 6-5), then
    diagonal votes are bucketed and the ``max_candidates`` most-supported
    diagonals returned.  Returns ``(starts [max_candidates] int32,
    votes [max_candidates] int32)``; empty slots have votes == 0.
    """
    is_min, h = minimizers(read, w=w, k=k)
    n_k = h.shape[0]
    score = jnp.where(is_min, h, jnp.uint32(0xFFFFFFFF))
    order = jnp.argsort(score)[:max_seeds]  # take up to max_seeds minimizers
    seed_pos = order.astype(jnp.int32)
    seed_hash = h[order]
    seed_valid = is_min[order]

    lo = jnp.searchsorted(idx_hashes, seed_hash, side="left")
    hi = jnp.searchsorted(idx_hashes, seed_hash, side="right")
    # take up to 4 index hits per seed
    hit_off = jnp.arange(4)[None, :]
    hit_idx = jnp.clip(lo[:, None] + hit_off, 0, idx_positions.shape[0] - 1)
    hit_ok = (lo[:, None] + hit_off < hi[:, None]) & seed_valid[:, None]
    ref_pos = idx_positions[hit_idx]
    diag = jnp.where(hit_ok, ref_pos - seed_pos[:, None], jnp.int32(-(2 ** 30)))
    diag = diag.reshape(-1)

    # bucket diagonals (tolerance via >> 5) and vote
    bucket = jnp.where(diag <= -(2 ** 29), jnp.int32(-(2 ** 30)), diag >> 5)
    sortb = jnp.sort(bucket)
    uniq_mask = jnp.concatenate([jnp.array([True]), sortb[1:] != sortb[:-1]])
    run_id = jnp.cumsum(uniq_mask) - 1
    votes = jnp.zeros((diag.shape[0],), jnp.int32).at[run_id].add(
        (sortb > -(2 ** 29)).astype(jnp.int32)
    )
    starts_sorted = jnp.zeros((diag.shape[0],), jnp.int32).at[run_id].max(
        jnp.where(sortb > -(2 ** 29), sortb << 5, -(2 ** 30))
    )
    top = jnp.argsort(-votes)[:max_candidates]
    return jnp.maximum(starts_sorted[top], 0), votes[top]
