"""End-to-end linear read mapper (paper Figure 2-2 with GenASM inside).

Seed-and-extend: MinSeed-style minimizer seeding → GenASM-DC pre-alignment
filter over candidates → windowed GenASM DC+TB alignment of the best
candidate.  Seeding + filtering is one jitted, vmapped stage
(:func:`seed_and_filter_batch`); the alignment stage is dispatched
through `repro.align.align_batch`, so every registered backend (pure
``lax``, the Pallas kernels, the ``ref`` oracle) drives the same
pipeline — the launcher shards reads over ``("pod", "data")`` with the
minimizer index replicated or sharded over ``"model"`` (DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import StageTimer

from .bitvector import SENTINEL, WILDCARD
from .genasm import GenASMConfig
from .genasm_dc import bitap_search
from .minimizer_index import ReferenceIndex, build_reference_index  # noqa: F401
from .segram.minimizer import seed_candidates

# lexicographic-selection sentinel: masked-out candidates sort last
POS_SENTINEL = jnp.iinfo(jnp.int32).max


class MapResult(NamedTuple):
    position: jnp.ndarray  # int32 mapped reference start (-1 if unmapped)
    distance: jnp.ndarray  # int32 edit distance (-1 if unmapped)
    ops: jnp.ndarray  # packed CIGAR
    n_ops: jnp.ndarray
    failed: jnp.ndarray


class SeedFilterResult(NamedTuple):
    position: jnp.ndarray  # int32 best candidate start (filter-refined)
    prefilter_ok: jnp.ndarray  # bool — candidate survived the filter
    text: jnp.ndarray  # [t_cap] int8 reference region at position
    t_len: jnp.ndarray  # int32 valid text length
    pattern: jnp.ndarray  # [p_cap] int8 wildcard-padded read
    # numpy default, not jnp: a device constant in the class body would
    # initialize the jax backend at module import, locking the device
    # count before XLA_FLAGS-based host-device forcing can apply.
    distance: jnp.ndarray = np.int32(0)  # int32 winning filter distance


def lex_best(fd: jnp.ndarray, fpos: jnp.ndarray) -> jnp.ndarray:
    """Index of the lexicographically-minimal ``(fd, fpos)`` candidate.

    The selection rule must be *shard-layout independent*: candidates
    merged from per-shard seeding (`repro.shard`) arrive in a different
    order than single-index seeding produces, so "argmin with
    first-wins ties" would pick different winners at 1 vs N shards.
    Minimizing ``(distance, position)`` makes the winner a pure
    function of the candidate *set*, and collapses the duplicate
    candidates that shard-overlap margins produce (identical
    ``(fd, fpos)`` pairs dedup to whichever index argmin returns —
    their downstream alignment windows are byte-identical).
    """
    pm = jnp.where(fd == jnp.min(fd), fpos, POS_SENTINEL)
    return jnp.argmin(pm)


def seed_filter_read(
    ref_buf: jnp.ndarray,
    ref_offset,
    ref_len: int,
    hashes: jnp.ndarray,
    positions: jnp.ndarray,
    read: jnp.ndarray,
    read_len,
    *,
    p_cap: int,
    t_cap: int,
    filter_bits: int,
    filter_k: int,
    max_candidates: int,
    minimizer_w: int,
    minimizer_k: int,
) -> SeedFilterResult:
    """Seed + pre-alignment-filter one read against one reference buffer.

    ``ref_buf`` is an ``[Lb] int8`` reference slice whose first base sits
    at global coordinate ``ref_offset`` of a reference of total length
    ``ref_len``; ``hashes``/``positions`` are a sorted minimizer table
    whose positions are *global* coordinates.  The whole-reference
    mapper calls this with ``ref_offset=0`` and the sharded mapper with
    each shard's haloed slice — the shared body is what keeps 1-shard
    and N-shard filter distances, refined positions, and window bytes
    bit-identical (positions are compared and emitted in global
    coordinates throughout).

    Returns a :class:`SeedFilterResult` whose ``position`` is the
    global refined start of the lexicographically best ``(distance,
    position)`` candidate (``POS_SENTINEL`` if the read produced no
    seed hits), with the ``[t_cap]`` alignment text sliced from
    ``ref_buf``.
    """
    # named scopes label the device ops of each stage in the profiler's
    # op metadata (they change no generated code)
    with jax.named_scope("seeding"):
        starts, votes = seed_candidates(
            read, hashes, positions,
            w=minimizer_w, k=minimizer_k, max_candidates=max_candidates,
        )
    # candidate starts are diagonal-bucketed to 32 (minimizer voting), so the
    # filter window must absorb bucket quantization + k edits of drift
    margin = filter_k + 32

    # --- pre-alignment filter (use case 2): exact distance of the read's
    # first filter_bits bases against each candidate region prefix.
    def filt(s):
        s0 = jnp.clip(s - margin, 0, jnp.maximum(ref_len - 1, 0))
        region = jax.lax.dynamic_slice(
            region_pad, (s0 - ref_offset,), (filter_bits + 2 * margin,))
        dists = bitap_search(region, fpat, m_bits=filter_bits, k=filter_k)
        return jnp.min(dists), s0 + jnp.argmin(dists).astype(jnp.int32)

    with jax.named_scope("dc_filter"):
        fpat = jnp.where(
            jnp.arange(filter_bits) < jnp.minimum(read_len, filter_bits),
            read[:filter_bits], WILDCARD,
        ).astype(jnp.int8)
        region_pad = jnp.concatenate(
            [ref_buf,
             jnp.full((filter_bits + 2 * margin,), SENTINEL, jnp.int8)])
        fd, fpos = jax.vmap(filt)(starts)
        fd = jnp.where(votes > 0, fd, filter_k + 1)
        fpos = jnp.where(votes > 0, fpos, POS_SENTINEL)
        best = lex_best(fd, fpos)
    pos = fpos[best]
    prefilter_ok = fd[best] <= filter_k

    text = jax.lax.dynamic_slice(
        jnp.concatenate([ref_buf, jnp.full((t_cap,), SENTINEL, jnp.int8)]),
        (jnp.minimum(pos, ref_len) - ref_offset,), (t_cap,),
    )
    r = read[:p_cap]
    if r.shape[0] < p_cap:
        r = jnp.pad(r, (0, p_cap - r.shape[0]), constant_values=WILDCARD)
    pat = jnp.where(jnp.arange(p_cap) < read_len, r, WILDCARD).astype(jnp.int8)
    return SeedFilterResult(
        position=pos.astype(jnp.int32),
        prefilter_ok=prefilter_ok,
        text=text,
        t_len=jnp.clip(ref_len - pos, 0, t_cap).astype(jnp.int32),
        pattern=pat,
        distance=fd[best].astype(jnp.int32),
    )


def _seed_and_filter_one(
    index: ReferenceIndex,
    read: jnp.ndarray,
    read_len,
    *,
    p_cap: int,
    t_cap: int,
    filter_bits: int,
    filter_k: int,
    max_candidates: int,
    minimizer_w: int,
    minimizer_k: int,
) -> SeedFilterResult:
    return seed_filter_read(
        index.ref, jnp.int32(0), index.ref.shape[0],
        index.hashes, index.positions, read, read_len,
        p_cap=p_cap, t_cap=t_cap, filter_bits=filter_bits,
        filter_k=filter_k, max_candidates=max_candidates,
        minimizer_w=minimizer_w, minimizer_k=minimizer_k)


@partial(
    jax.jit,
    static_argnames=(
        "p_cap", "t_cap", "filter_bits", "filter_k", "max_candidates",
        "minimizer_w", "minimizer_k",
    ),
)
def seed_and_filter_batch(index, reads, read_lens, *, p_cap, t_cap,
                          filter_bits, filter_k, max_candidates,
                          minimizer_w, minimizer_k) -> SeedFilterResult:
    """Vmapped seeding + pre-alignment filtering (one jit per shape)."""
    f = partial(
        _seed_and_filter_one, index, p_cap=p_cap, t_cap=t_cap,
        filter_bits=filter_bits, filter_k=filter_k,
        max_candidates=max_candidates, minimizer_w=minimizer_w,
        minimizer_k=minimizer_k)
    return jax.vmap(f)(reads, read_lens)


def map_batch(
    index: ReferenceIndex,
    reads: jnp.ndarray,
    read_lens: jnp.ndarray,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    p_cap: int = 256,
    filter_bits: int = 128,
    filter_k: int = 12,
    max_candidates: int = 4,
    minimizer_w: int = 10,
    minimizer_k: int = 15,
    backend: str | None = None,
    block_bt: int | None = None,
) -> MapResult:
    """Map a read batch against the indexed reference.

    ``backend`` selects the alignment implementation by registry name
    (`repro.align`); None/"auto" resolves per platform.
    """
    from repro import align as align_dispatch

    t_cap = p_cap + cfg.w * 2
    sf = seed_and_filter_batch(
        index, reads, read_lens.astype(jnp.int32), p_cap=p_cap, t_cap=t_cap,
        filter_bits=filter_bits, filter_k=filter_k,
        max_candidates=max_candidates, minimizer_w=minimizer_w,
        minimizer_k=minimizer_k)

    res = align_dispatch.align_batch(
        sf.text, sf.pattern, read_lens.astype(jnp.int32), sf.t_len,
        cfg=cfg, backend=backend, p_cap=p_cap, block_bt=block_bt)
    failed = res.failed | (~sf.prefilter_ok)
    return MapResult(
        position=jnp.where(failed, -1, sf.position).astype(jnp.int32),
        distance=jnp.where(failed, -1, res.distance),
        ops=res.ops,
        n_ops=res.n_ops,
        failed=failed,
    )


def map_read(index: ReferenceIndex, read: jnp.ndarray, read_len, **kw
             ) -> MapResult:
    """Map one read (batch-of-one convenience wrapper)."""
    res = map_batch(index, read[None], jnp.asarray(read_len)[None], **kw)
    return jax.tree_util.tree_map(lambda x: x[0], res)


class LinearMapExecutor:
    """Two-stage compiled linear mapper: seed/filter stage + align stage.

    Computes exactly what `map_batch` computes (same ops, same integer
    math — PAF output is byte-identical), but jits the seed+filter and
    align stages *separately* so the host can time each one: every call
    records ``last_times`` — ``(stage, t_start, t_end, attrs)`` on the
    monotonic clock, with a ``compile`` attr flagging calls that traced
    — which the serve engine replays into its tracer (`repro.obs`,
    DESIGN.md §12).  The stage boundary materializes one
    `SeedFilterResult`, a per-flush cost measured at <1% of the stage
    itself on the smoke benchmark.

    ``trace_hook`` (if given) is called with ``("seed_filter",)`` /
    ``("align",)`` at trace time, mirroring `GraphMapExecutor`'s stage
    keys so retrace accounting is uniform across workloads.
    """

    def __init__(self, *, cfg: GenASMConfig = GenASMConfig(),
                 p_cap: int = 256,
                 filter_bits: int = 128,
                 filter_k: int = 12,
                 max_candidates: int = 4,
                 minimizer_w: int = 10,
                 minimizer_k: int = 15,
                 backend: str | None = None,
                 block_bt: int | None = None,
                 trace_hook=None):
        from repro import align as align_dispatch

        t_cap = p_cap + cfg.w * 2
        user_hook = trace_hook or (lambda key: None)
        self._compiled: set = set()

        def hook(key):
            self._compiled.add(key)
            user_hook(key)

        def sf_fn(index, reads, lens):
            hook(("seed_filter",))
            return seed_and_filter_batch(
                index, reads, lens.astype(jnp.int32), p_cap=p_cap,
                t_cap=t_cap, filter_bits=filter_bits, filter_k=filter_k,
                max_candidates=max_candidates, minimizer_w=minimizer_w,
                minimizer_k=minimizer_k)

        def align_fn(sf, lens):
            hook(("align",))
            res = align_dispatch.align_batch(
                sf.text, sf.pattern, lens.astype(jnp.int32), sf.t_len,
                cfg=cfg, backend=backend, p_cap=p_cap, block_bt=block_bt)
            failed = res.failed | (~sf.prefilter_ok)
            return MapResult(
                position=jnp.where(failed, -1, sf.position).astype(jnp.int32),
                distance=jnp.where(failed, -1, res.distance),
                ops=res.ops, n_ops=res.n_ops, failed=failed)

        self._sf = jax.jit(sf_fn)
        self._align = jax.jit(align_fn)
        self.last_times: list[tuple[str, float, float, dict]] = []

    def __call__(self, index: ReferenceIndex, reads, read_lens) -> MapResult:
        lens = jnp.asarray(read_lens)
        timer = StageTimer()
        # a stage not traced before traces (compiles) in this call
        with timer.stage("seed_filter",
                         compile=("seed_filter",) not in self._compiled):
            sf = self._sf(index, jnp.asarray(reads), lens)
            jax.block_until_ready(sf)
        with timer.stage("align", compile=("align",) not in self._compiled):
            res = self._align(sf, lens)
            jax.block_until_ready(res)
        self.last_times = timer.times
        return res
