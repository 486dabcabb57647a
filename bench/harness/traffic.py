"""The one traffic generator: reads and arrivals from a mix file and a seed.

A mix file (``bench/traffic/<name>.json``) holds parameters only:

* ``reads``: length classes, ``[{"length": 150, "share": 0.8}, ...]``;
  every seed gets the same number of reads of each class, in its own
  order;
* ``error_profile``: Mason-style edits per source base, ``rate`` split
  into ``sub``/``ins``/``del`` shares (an insertion puts a random base
  before the source base, a deletion drops it);
* ``arrival``: ``"backlog"`` keeps ``outstanding_batches`` times the
  engine's ``max_batch`` reads outstanding for the whole window, from a
  pool of ``pool_reads_per_s`` times the window's seconds;
  ``"poisson"`` offers ``rate_reads_per_s`` open-loop, with the same
  multiset of exponential gaps for every seed, shuffled by the seed;
* ``warmup_reads_per_length``: reads of each class kept apart for
  warm-up, so that no window read is ever a result-cache hit.

Reads are drawn uniformly from a ``Source``: the deployment's backbone
sequence, or a sample's haplotype with a map from a start on it to the
backbone coordinate that ``true_pos`` records.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

CHUNK = 8192  # reads simulated per vectorized step


class Reads(NamedTuple):
    """Ragged reads: ``flat[offsets[i]:offsets[i+1]]`` is read ``i``."""

    flat: np.ndarray  # int8 bases 0..3
    offsets: np.ndarray  # [n+1] int64
    true_pos: np.ndarray  # [n] int64 source start on the backbone

    def __len__(self) -> int:
        return len(self.true_pos)

    def read(self, i: int) -> np.ndarray:
        return self.flat[self.offsets[i]:self.offsets[i + 1]]

    @property
    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets)


class Source(NamedTuple):
    """The sequence reads are drawn from; ``to_backbone`` maps a read's
    start on it to its ``true_pos`` (``None``: it is the backbone)."""

    seq: np.ndarray
    to_backbone: Callable[[np.ndarray], np.ndarray] | None = None


def _simulate(ref: np.ndarray, n: int, length: int, prof: dict,
              rng: np.random.Generator):
    pos = rng.integers(0, len(ref) - length, size=n)
    src = ref[pos[:, None] + np.arange(length)[None, :]]
    err = rng.random((n, length)) < prof["rate"]
    kind = rng.random((n, length))
    sub = err & (kind < prof["sub"])
    ins = err & (kind >= prof["sub"]) & (kind < prof["sub"] + prof["ins"])
    dele = err & (kind >= prof["sub"] + prof["ins"])
    shift = rng.integers(1, 4, size=(n, length), dtype=np.int8)
    base = np.where(sub, (src + shift) % 4, src).astype(np.int8)
    extra = rng.integers(0, 4, size=(n, length), dtype=np.int8)
    # per source base: an inserted base before it, then itself unless
    # deleted; row-major order keeps each read's bases together
    tok = np.stack([extra, base], axis=-1).reshape(n, 2 * length)
    keep = np.stack([ins, ~dele], axis=-1).reshape(n, 2 * length)
    return tok[keep], keep.sum(axis=1), pos


def simulate_reads(ref: np.ndarray, lengths: np.ndarray, profile: dict,
                   rng: np.random.Generator, to_backbone=None) -> Reads:
    """One read per entry of ``lengths`` (source bases), in that order.

    Each chunk of reads is simulated one length class at a time, then
    put back in ``lengths``' order.  ``to_backbone`` maps each read's
    start on ``ref`` to its ``true_pos``.
    """
    flats, lens, poss = [], [], []
    for s in range(0, len(lengths), CHUNK):
        part = lengths[s:s + CHUNK]
        pieces = [None] * len(part)
        lens_p = np.empty(len(part), np.int64)
        pos_p = np.empty(len(part), np.int64)
        for length in np.unique(part):
            where = np.nonzero(part == length)[0]
            flat, n_out, pos = _simulate(ref, len(where), int(length),
                                         profile, rng)
            off = np.concatenate([[0], np.cumsum(n_out)])
            for j, w in enumerate(where):
                pieces[w] = flat[off[j]:off[j + 1]]
            lens_p[where] = n_out
            pos_p[where] = pos
        flats.append(np.concatenate(pieces))
        lens.append(lens_p)
        poss.append(pos_p)
    lens_all = np.concatenate(lens) if lens else np.zeros(0, np.int64)
    pos_all = np.concatenate(poss) if poss else np.zeros(0, np.int64)
    return Reads(
        flat=(np.concatenate(flats) if flats else np.zeros(0, np.int8)),
        offsets=np.concatenate([[0], np.cumsum(lens_all)]).astype(np.int64),
        true_pos=(pos_all if to_backbone is None
                  else to_backbone(pos_all).astype(np.int64)))


def class_lengths(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` source lengths with each class's exact share, seed-shuffled."""
    classes = mix["reads"]
    counts = [int(round(c["share"] * n)) for c in classes]
    counts[-1] = n - sum(counts[:-1])
    out = np.concatenate([np.full(k, c["length"], np.int64)
                          for c, k in zip(classes, counts)])
    rng.shuffle(out)
    return out


def poisson_offsets(rate: float, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times in ``[0, seconds]`` of ``rate * seconds`` arrivals.

    The gaps are the midpoint quantiles of the exponential distribution,
    shuffled by ``rng`` and scaled so that the last read is due at the
    window's close: every seed offers the same load with the same
    spread of gaps, in a different order.
    """
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    rng.shuffle(gaps)
    return np.cumsum(gaps) * (seconds / gaps.sum())


class Plan(NamedTuple):
    """What one run offers: warm-up reads, window reads, due times."""

    warmup: Reads
    window: Reads
    due: np.ndarray | None  # [n] seconds after the window opens (poisson)
    outstanding: int  # reads kept in flight (backlog), else 0


def plan(mix: dict, source: Source, *, seconds: float, max_batch: int,
         rng_warm: np.random.Generator, rng_window: np.random.Generator,
         rng_arrival: np.random.Generator) -> Plan:
    """Everything a run of ``mix`` offers, drawn from three seeded streams."""
    prof = mix["error_profile"]
    warm_n = mix["warmup_reads_per_length"]
    warm_len = np.repeat([c["length"] for c in mix["reads"]], warm_n)
    warmup = simulate_reads(source.seq, warm_len, prof, rng_warm,
                            source.to_backbone)
    if mix["arrival"] == "backlog":
        n = int(np.ceil(mix["pool_reads_per_s"] * seconds))
        due, outstanding = None, mix["outstanding_batches"] * max_batch
    elif mix["arrival"] == "poisson":
        due = poisson_offsets(mix["rate_reads_per_s"], seconds, rng_arrival)
        n, outstanding = len(due), 0
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    window = simulate_reads(source.seq, class_lengths(mix, n, rng_window),
                            prof, rng_window, source.to_backbone)
    return Plan(warmup, window, due, outstanding)
