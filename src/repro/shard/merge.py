"""On-device shard merge: packed monotone uint64 keys + argmin-reduce.

The PR-5 sharded mappers merged per-shard winners on the host — every
``[S, B]`` distance/position/window array round-tripped device → host
numpy → device between the filter and align stages, exactly the
accelerator-to-host data movement the dissertation's GenASM co-design
removes by keeping the DC→TB handoff on-accelerator.  This module
replaces that host step with a device reduction:

* `pack_linear_key` / `pack_graph_key` pack one candidate's
  lexicographic sort tuple — ``(distance, position)`` for the linear
  workload, ``(distance, origin, tile)`` for the graph workload — into
  a single **monotone** ``uint64``: ``a < b`` tuple-wise iff
  ``pack(a) < pack(b)``.  Sentinel components (`POS_SENTINEL`, the
  "no candidate" marker) map to the top of their bit field, so masked
  candidates sort last, exactly like the host rule.
* `merge_linear` / `merge_graph` take the stacked ``[S, B, ...]`` stage
  outputs, ``argmin`` the packed key over the shard axis, and gather
  the winner row per read.  ``jnp.argmin`` returns the *first* minimal
  index, which reproduces `repro.core.mapper.lex_best`'s tie-break
  (lowest shard wins on a full-key tie) bit for bit — proven
  differentially by ``tests/test_shard_merge.py``.

JAX runs with ``x64`` disabled globally, so the 64-bit key only exists
inside a `jax.enable_x64` scope: wrap calls to the jitted
merge in `x64_scope` (the executors do).  Inputs and outputs are plain
``int32`` arrays, so nothing 64-bit leaks to callers.  The pack/unpack
helpers are dtype-driven (``.astype``/shift/mask only), so they run
unchanged on numpy ``uint64`` arrays — which is how the property suite
checks order-isomorphism without touching the x64 flag.

Field layout (bit widths chosen once, validated by `check_graph_domain`):

    linear  key = distance[32] . position[32]
    graph   key = distance[12] . origin[31]  . tile[21]

``origin``'s 31-bit field tops out at ``2**31 - 1 == POS_SENTINEL``
itself, so sentinel origins need no remapping; tile sentinels clamp to
the 21-bit field max and `unpack_graph_key` restores them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.mapper import POS_SENTINEL

# graph key bit layout: 12 + 31 + 21 = 64
GRAPH_D_BITS = 12
GRAPH_ORIGIN_BITS = 31
GRAPH_TILE_BITS = 21
GRAPH_D_MAX = (1 << GRAPH_D_BITS) - 1
GRAPH_ORIGIN_MAX = (1 << GRAPH_ORIGIN_BITS) - 1  # == POS_SENTINEL
GRAPH_TILE_MAX = (1 << GRAPH_TILE_BITS) - 1  # sentinel encoding for tiles


def x64_scope():
    """Context manager enabling 64-bit types for the packed merge keys."""
    return jax.enable_x64(True)


def pack_linear_key(distance, position):
    """Monotone uint64 key for the linear ``(distance, position)`` tuple.

    Valid for non-negative int32 components (positions use
    `POS_SENTINEL` for "none", which already sorts last).  Works on
    jnp arrays inside an `x64_scope` and on numpy arrays as-is.
    """
    return ((distance.astype("uint64") << 32)
            | position.astype("uint64"))


def unpack_linear_key(key):
    """Inverse of `pack_linear_key`: ``(distance, position)`` int32."""
    return ((key >> 32).astype("int32"),
            (key & ((1 << 32) - 1)).astype("int32"))


def pack_graph_key(distance, origin, tile):
    """Monotone uint64 key for the graph ``(distance, origin, tile)`` tuple.

    Domain (validated once per geometry by `check_graph_domain`):
    ``distance <= GRAPH_D_MAX``, ``origin < POS_SENTINEL`` or exactly
    `POS_SENTINEL` (the 31-bit field max, so the sentinel is its own
    encoding), ``tile < GRAPH_TILE_MAX`` or `POS_SENTINEL` (clamped to
    the 21-bit field max).  Dead candidates carry sentinel origin *and*
    tile (same ``live`` mask upstream), which keeps the packed argmin
    equal to the host three-level masked merge.
    """
    t = tile.clip(0, GRAPH_TILE_MAX)
    return ((distance.astype("uint64") << (GRAPH_ORIGIN_BITS
                                           + GRAPH_TILE_BITS))
            | (origin.astype("uint64") << GRAPH_TILE_BITS)
            | t.astype("uint64"))


def unpack_graph_key(key):
    """Inverse of `pack_graph_key`: ``(distance, origin, tile)`` int32.

    Tile field-max decodes back to `POS_SENTINEL` (the only value the
    clamp can have mapped there, per the `check_graph_domain` bound).
    """
    d = (key >> (GRAPH_ORIGIN_BITS + GRAPH_TILE_BITS)).astype("int32")
    origin = ((key >> GRAPH_TILE_BITS) & GRAPH_ORIGIN_MAX).astype("int32")
    t = (key & GRAPH_TILE_MAX).astype("int32")
    tile = t + (t == GRAPH_TILE_MAX) * (POS_SENTINEL - GRAPH_TILE_MAX)
    return d, origin, tile.astype("int32")


def check_graph_domain(*, n_tiles: int, filter_k: int) -> None:
    """Raise if a graph geometry cannot round-trip through the key fields.

    ``n_tiles`` must leave the 21-bit field max free for the sentinel
    and ``filter_k + 1`` (the "no candidate" distance) must fit the
    12-bit distance field — generous bounds (2M tiles, distance 4094)
    for any geometry the bucket ladder serves, but checked rather than
    assumed.
    """
    if n_tiles >= GRAPH_TILE_MAX:
        raise ValueError(
            f"graph index has {n_tiles} tiles but the packed merge key's "
            f"tile field holds {GRAPH_TILE_MAX - 1} + sentinel; shard the "
            f"graph or widen GRAPH_TILE_BITS")
    if filter_k + 1 > GRAPH_D_MAX:
        raise ValueError(
            f"filter_k {filter_k} overflows the packed merge key's "
            f"{GRAPH_D_BITS}-bit distance field (max {GRAPH_D_MAX - 1})")


def _gather_winner(arr, win):
    """``arr[win[b], b, ...]`` for stacked ``[S, B, ...]`` leaves."""
    idx = win.reshape((1,) + win.shape + (1,) * (arr.ndim - 2))
    return jnp.take_along_axis(arr, idx, axis=0)[0]


def merge_linear(distance, position, text, t_len):
    """Device argmin-reduce of stacked linear shard winners.

    Same contract as the host ``ShardedMapExecutor.merge`` —
    ``(fd, pos, text, t_len, winner_shard)`` per read, tie-breaking
    bit-identical to `lex_best` — but as one jittable program over the
    ``[S, B, ...]`` stage outputs, so the winners never leave the
    device between filter and align.  Call inside `x64_scope`.
    """
    key = pack_linear_key(distance, position)  # [S, B] uint64
    win = jnp.argmin(key, axis=0).astype(jnp.int32)  # first min = low shard
    return (_gather_winner(distance, win), _gather_winner(position, win),
            _gather_winner(text, win), _gather_winner(t_len, win), win)


def merge_graph(distance, origin, tile, gwin, bwin, t_len, prefilter_ok):
    """Device argmin-reduce of stacked graph shard winners.

    Field-by-field twin of the host ``ShardedGraphMapExecutor.merge``
    (three-level ``(distance, origin, tile)`` lexicographic min): the
    packed-key argmin picks the same shard because dead candidates
    carry sentinel origin *and* tile together (the stage's shared
    ``live`` mask), so masking and key order agree.  Returns the
    merged per-read fields plus the winner shard.  Call inside
    `x64_scope`.
    """
    key = pack_graph_key(distance, origin, tile)  # [S, B] uint64
    win = jnp.argmin(key, axis=0).astype(jnp.int32)
    pick = lambda a: _gather_winner(a, win)  # noqa: E731
    return (pick(distance), pick(origin), pick(tile), pick(gwin),
            pick(bwin), pick(t_len), pick(prefilter_ok), win)
