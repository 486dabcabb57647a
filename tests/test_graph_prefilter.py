"""Tile pre-filter soundness + ragged-gather/bucket-ladder properties.

The q-gram tile screen (`graph.mapper.tile_prefilter`) may only remove
candidate tiles that the exact GenASM-DC filter would reject anyway —
otherwise GAF output would change with the screen on.  This suite proves
that three ways:

  * **differential vs the exact filter** — no slot whose dense in-span
    DC distance is ≤ k is ever pruned, across edit budgets;
  * **differential vs the DP oracle** — the tile containing the
    oracle-best mapping (``oracle.graph_edit_distance_anchored``) is
    never pruned for any edit budget that admits that mapping;
  * **end-to-end** — prefilter on/off produce identical
    `GraphMapResult`s, including node paths, on mixed clean/mutated/
    garbage batches.

Plus the screen's monotonicity in k, the argsort-compaction round-trip
invariants (every survivor gathered exactly once, padding never
scattered into a live slot), the zero-survivor short-circuit, and the
serve engine's (read-length, tile-count) bucket ladder compiling once
per rung pair.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import filter as qfilter
from repro.core import oracle
from repro.core.genasm import GenASMConfig
from repro.core.segram import graph as cgraph
from repro.genomics import encode, simulate
from repro.graph import index as gindex
from repro.graph import mapper as gmapper
from repro.serve import EngineConfig, ServeEngine

CFG = GenASMConfig()
P_CAP = 128
T_CAP = P_CAP + 2 * CFG.w
FILTER_K = 12
L = 6_000
MAX_CAND = 4
SEED_KW = dict(minimizer_w=8, minimizer_k=12)


@pytest.fixture(scope="module")
def graph_setup():
    ref = simulate.random_reference(L, seed=41)
    variants = simulate.simulate_variants(ref, n_snp=20, n_ins=10,
                                          n_del=10, seed=42)
    gidx = gindex.build_graph_index(ref, variants, w=8, k=12,
                                    window=T_CAP)
    return ref, variants, gidx


def _mixed_reads(ref, *, seed, n_clean=6, n_mut=6, n_garbage=4,
                 read_len=100):
    """Clean / mutated / unmappable reads, encoded to [B, P_CAP]."""
    rng = np.random.default_rng(seed)
    reads = []
    for i in range(n_clean + n_mut):
        s = int(rng.integers(0, len(ref) - read_len))
        r = np.array(ref[s: s + read_len], np.int8)
        if i >= n_clean:
            subs = rng.integers(0, read_len, size=4)
            r[subs] = (r[subs] + 1 + rng.integers(0, 3, size=4)) % 4
        reads.append(r)
    for _ in range(n_garbage):
        reads.append(rng.integers(0, 4, read_len).astype(np.int8))
    return encode.batch_reads(reads, P_CAP)


def _pf_kw(gidx, filter_k=FILTER_K, prefilter=True):
    return dict(tile_stride=gidx.tile_stride, n_tiles=gidx.n_tiles,
                backbone_len=gidx.arrays.node_of_backbone.shape[0],
                filter_bits=P_CAP, filter_k=filter_k,
                max_candidates=MAX_CAND, prefilter=prefilter, **SEED_KW)


def _dense_slot_dists(gidx, arr, lens, pf, filter_k):
    """Every slot's dense in-span DC distance (the exact filter verdict)."""
    view = gmapper.whole_graph_view(gidx.arrays)
    b, c = pf.votes.shape
    span = gidx.tile_stride + gidx.margin  # the mapper's anchor span
    tile_g, tile_local = gmapper._tiles_of_starts(
        view, pf.starts, tile_stride=gidx.tile_stride, n_tiles=gidx.n_tiles,
        backbone_len=gidx.arrays.node_of_backbone.shape[0])
    fpat, flens = gmapper._filter_pattern(jnp.asarray(arr),
                                          jnp.asarray(lens, jnp.int32),
                                          P_CAP)
    wins = gindex.gather_windows(view.gtext, tile_local * gidx.tile_stride,
                                 T_CAP + span, n_nodes=gidx.n_nodes)
    d, _ = gmapper._filter_best(
        wins.reshape(b * c, T_CAP + span), jnp.repeat(fpat, c, axis=0),
        jnp.repeat(flens, c), span=span, m_bits=P_CAP,
        k=filter_k, use_kernel=False, block_bt=None, interpret=True)
    return np.asarray(d).reshape(b, c), np.asarray(tile_g)


# ------------------------------------------------------------- soundness --
@pytest.mark.parametrize("filter_k", [4, 8, 12])
def test_screen_never_prunes_dc_passing_tiles(graph_setup, filter_k):
    """Differential vs the exact filter: prune ⇒ dense DC distance > k,
    for every candidate slot, at every edit budget."""
    ref, _, gidx = graph_setup
    arr, lens = _mixed_reads(ref, seed=50 + filter_k)
    view = gmapper.whole_graph_view(gidx.arrays)
    pf = gmapper.tile_prefilter(view, jnp.asarray(arr),
                                jnp.asarray(lens, jnp.int32),
                                **_pf_kw(gidx, filter_k=filter_k))
    d_slot, _ = _dense_slot_dists(gidx, arr, lens, pf, filter_k)
    live = np.asarray(pf.votes) > 0
    keep = np.asarray(pf.keep)
    # every live slot the exact filter accepts must survive the screen
    bad = live & (d_slot <= filter_k) & ~keep
    assert not bad.any(), \
        f"screen pruned DC-passing slots at k={filter_k}: {np.argwhere(bad)}"
    # and the screen must actually be a subset of live
    assert not (keep & ~live).any()


def test_oracle_best_tile_never_pruned(graph_setup):
    """The tile holding the oracle-best anchored mapping survives the
    screen at every edit budget ≥ the oracle distance."""
    ref, variants, gidx = graph_setup
    g = cgraph.build_graph(ref, list(variants))  # the index's own graph
    rng = np.random.default_rng(77)
    view = gmapper.whole_graph_view(gidx.arrays)
    nob = np.asarray(gidx.arrays.node_of_backbone)
    checked = 0
    reads, anchors = [], []
    for _ in range(12):
        p = int(rng.integers(0, L - 200))
        m = int(rng.integers(60, 96))
        read = np.array(ref[p: p + m], np.int8)
        n_sub = int(rng.integers(0, 4))
        for _ in range(n_sub):
            j = int(rng.integers(0, m))
            read[j] = (read[j] + 1 + rng.integers(0, 3)) % 4
        reads.append(read)
        anchors.append(p)
    arr, lens = encode.batch_reads(reads, P_CAP)

    # oracle-anchored distance of each read at its true backbone locus
    tile_stride = gidx.tile_stride
    for k in (6, 12):
        pf = gmapper.tile_prefilter(view, jnp.asarray(arr),
                                    jnp.asarray(lens, jnp.int32),
                                    **_pf_kw(gidx, filter_k=k))
        tile_g, _ = gmapper._tiles_of_starts(
            view, pf.starts, tile_stride=tile_stride, n_tiles=gidx.n_tiles,
            backbone_len=nob.shape[0])
        tile_g = np.asarray(tile_g)
        live = np.asarray(pf.votes) > 0
        keep = np.asarray(pf.keep)
        for i, (read, p) in enumerate(zip(reads, anchors)):
            node = int(nob[p])
            sub_b, sub_s = cgraph.extract_subgraph(g, node, T_CAP)
            sub = cgraph.GenomeGraph(sub_b, sub_s,
                                     np.zeros(T_CAP, np.int32),
                                     np.zeros(0, np.int32))
            d_star = oracle.graph_edit_distance_anchored(
                read, sub_b, cgraph.predecessors(sub), start=0)
            if d_star > k:
                continue  # budget does not admit the mapping
            true_tile = node // tile_stride
            hit = live[i] & (tile_g[i] == true_tile)
            if not hit.any():
                continue  # seeding never offered the true tile
            assert keep[i][hit].any(), \
                (f"read {i}: oracle d*={d_star} ≤ k={k} but every slot of "
                 f"tile {true_tile} was pruned")
            checked += 1
    assert checked >= 10  # the property was actually exercised


def test_screen_monotone_in_k(graph_setup):
    """keep(k₁) ⊆ keep(k₂) for k₁ ≤ k₂ — raising the budget never
    prunes more."""
    ref, _, gidx = graph_setup
    arr, lens = _mixed_reads(ref, seed=60)
    view = gmapper.whole_graph_view(gidx.arrays)
    prev = None
    for k in (2, 4, 8, 12, 16):
        pf = gmapper.tile_prefilter(view, jnp.asarray(arr),
                                    jnp.asarray(lens, jnp.int32),
                                    **_pf_kw(gidx, filter_k=k))
        keep = np.asarray(pf.keep)
        if prev is not None:
            assert not (prev & ~keep).any(), f"screen not monotone at k={k}"
        prev = keep


def test_prefilter_on_off_results_identical(graph_setup):
    """Full GraphMapResult equality — positions, distances, CIGAR ops,
    node paths, failure flags — with the screen on and off."""
    ref, _, gidx = graph_setup
    arr, lens = _mixed_reads(ref, seed=70)
    kw = dict(cfg=CFG, p_cap=P_CAP, filter_bits=P_CAP, filter_k=FILTER_K,
              max_candidates=MAX_CAND, backend="graph_lax", **SEED_KW)
    on = gmapper.map_batch_index(gidx, jnp.asarray(arr), jnp.asarray(lens),
                                 prefilter=True, **kw)
    off = gmapper.map_batch_index(gidx, jnp.asarray(arr), jnp.asarray(lens),
                                  prefilter=False, **kw)
    for f in on._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(on, f)), np.asarray(getattr(off, f)),
            err_msg=f"prefilter on/off diverge on {f}")
    assert (np.asarray(on.position) >= 0).sum() >= 10  # batch actually maps


# ------------------------------------------- ragged gather / compaction --
def test_compaction_round_trip_invariants():
    """The argsort compaction gathers every survivor exactly once, in
    slot order, and scatter-back touches only survivor slots."""
    rng = np.random.default_rng(5)
    b, c = 16, 4
    bc = b * c
    keep = rng.random((b, c)) < 0.3
    kf = keep.reshape(bc)
    n_tot = int(kf.sum())
    n_cap = gmapper.tile_rung(n_tot, bc)
    # the stage's exact compaction arithmetic
    order = np.argsort(np.where(kf, 0, bc) + np.arange(bc), kind="stable")
    slots = order[:n_cap]
    # every survivor appears exactly once, before any non-survivor,
    # in increasing slot order
    assert n_cap >= n_tot
    assert sorted(slots[:n_tot]) == list(np.flatnonzero(kf))
    assert (np.diff(slots[:n_tot]) > 0).all()
    assert not kf[slots[n_tot:]].any()  # tail rows are padding only
    # scatter-back: padding rows write the dense defaults, so only
    # survivor slots can carry a real distance
    d_r = rng.integers(0, FILTER_K + 1, n_cap)
    rowmask = np.arange(n_cap) < n_tot
    d_c = np.full(bc, FILTER_K + 1)
    d_c[slots] = np.where(rowmask, d_r, FILTER_K + 1)
    assert (d_c[~kf] == FILTER_K + 1).all(), "padding scattered into a slot"
    assert (d_c[slots[:n_tot]] == d_r[:n_tot]).all()


def test_compacted_stage_matches_dense_at_any_rung(graph_setup):
    """graph_candidate_stage with pf/n_cap equals the dense legacy path
    on every winner field, at the high-water rung and at full cap."""
    ref, _, gidx = graph_setup
    arr, lens = _mixed_reads(ref, seed=80)
    view = gmapper.whole_graph_view(gidx.arrays)
    skw = dict(tile_stride=gidx.tile_stride, margin=gidx.margin,
               n_tiles=gidx.n_tiles,
               backbone_len=gidx.arrays.node_of_backbone.shape[0],
               n_nodes=gidx.n_nodes, t_cap=T_CAP, filter_bits=P_CAP,
               filter_k=FILTER_K, max_candidates=MAX_CAND, **SEED_KW)
    reads_j = jnp.asarray(arr)
    lens_j = jnp.asarray(lens, jnp.int32)
    dense = gmapper.graph_candidate_stage(view, reads_j, lens_j, **skw)
    pf = gmapper.tile_prefilter(view, reads_j, lens_j, **_pf_kw(gidx))
    total = int(np.asarray(pf.n_keep).sum())
    assert total > 0
    b = arr.shape[0]
    for n_cap in (gmapper.tile_rung(total, b * MAX_CAND), b * MAX_CAND):
        comp = gmapper.graph_candidate_stage(view, reads_j, lens_j, pf=pf,
                                             n_cap=n_cap, **skw)
        for f in ("distance", "origin", "tile", "t_len", "prefilter_ok"):
            np.testing.assert_array_equal(
                np.asarray(getattr(dense, f)), np.asarray(getattr(comp, f)),
                err_msg=f"compacted stage (n_cap={n_cap}) diverges on {f}")
        # window bytes agree wherever a live winner exists (dead winners
        # carry garbage that align_winners canonicalizes away)
        ok = np.asarray(dense.distance) <= FILTER_K
        np.testing.assert_array_equal(np.asarray(dense.gwin)[ok],
                                      np.asarray(comp.gwin)[ok])
        np.testing.assert_array_equal(np.asarray(dense.bwin)[ok],
                                      np.asarray(comp.bwin)[ok])


def test_tile_rung_ladder():
    assert gmapper.tile_rung(0, 128) == 0
    assert gmapper.tile_rung(-3, 128) == 0
    assert gmapper.tile_rung(1, 128) == 8  # floor rung
    assert gmapper.tile_rung(8, 128) == 8
    assert gmapper.tile_rung(9, 128) == 16
    assert gmapper.tile_rung(100, 128) == 128
    assert gmapper.tile_rung(500, 128) == 128  # clamped to dense cap
    for n in range(1, 130):
        r = gmapper.tile_rung(n, 128)
        assert r >= min(n, 128)  # never smaller than the survivors


# --------------------------------------------- zero-survivor short-circuit --
def test_zero_survivor_batch_short_circuits(graph_setup):
    """A batch where no read has surviving candidates skips DC and align
    entirely and still equals the prefilter-off result bitwise."""
    ref, _, gidx = graph_setup
    rng = np.random.default_rng(90)
    reads = [rng.integers(0, 4, 100).astype(np.int8) for _ in range(6)]
    arr, lens = encode.batch_reads(reads, P_CAP)
    kw = dict(geometry=gidx.geometry, cfg=CFG, p_cap=P_CAP,
              filter_bits=P_CAP, filter_k=FILTER_K,
              max_candidates=MAX_CAND, backend="graph_lax", **SEED_KW)
    ex_on = gmapper.GraphMapExecutor(prefilter=True, **kw)
    ex_off = gmapper.GraphMapExecutor(prefilter=False, **kw)
    r_on = ex_on(gidx.arrays, jnp.asarray(arr), jnp.asarray(lens))
    assert ex_on.last_stats["dc_rows"] == 0  # no DC launch at all
    assert ex_on.last_stats["reads_zero_survivor"] == len(reads)
    assert np.asarray(r_on.failed).all()
    assert (np.asarray(r_on.position) == -1).all()
    assert (np.asarray(r_on.n_ops) == 0).all()
    r_off = ex_off(gidx.arrays, jnp.asarray(arr), jnp.asarray(lens))
    for f in r_on._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(r_on, f)), np.asarray(getattr(r_off, f)),
            err_msg=f"zero-survivor short-circuit diverges on {f}")


def test_mixed_batch_zero_survivor_reads_stat(graph_setup):
    """Zero-survivor *reads* inside a live batch are counted and mapped
    to the canonical unmapped result."""
    ref, _, gidx = graph_setup
    rng = np.random.default_rng(91)
    reads = [np.array(ref[500:600], np.int8),
             rng.integers(0, 4, 100).astype(np.int8)]
    arr, lens = encode.batch_reads(reads, P_CAP)
    kw = dict(geometry=gidx.geometry, cfg=CFG, p_cap=P_CAP,
              filter_bits=P_CAP, filter_k=FILTER_K,
              max_candidates=MAX_CAND, backend="graph_lax", **SEED_KW)
    ex = gmapper.GraphMapExecutor(prefilter=True, **kw)
    res = ex(gidx.arrays, jnp.asarray(arr), jnp.asarray(lens))
    assert ex.last_stats["reads_zero_survivor"] >= 1
    assert 0 < ex.last_stats["dc_rows"] <= ex.last_stats["dc_rows_dense"]
    assert int(res.position[0]) == 500 and int(res.distance[0]) == 0
    assert bool(res.failed[1]) and int(res.n_ops[1]) == 0


# ----------------------------------------------------- serve bucket ladder --
def test_engine_graph_ladder_compiles_once_per_rung(graph_setup):
    """The engine's graph executors trace once per (read-length rung,
    tile-count rung) pair — prefilter/align once per cap, candidate
    stage once per rung — and never retrace on repeat traffic."""
    ref, variants, _ = graph_setup
    egi = gindex.build_epoched_graph_index(ref, variants, w=8, k=12,
                                           window=192 + 2 * CFG.w)
    cfg = EngineConfig(buckets=(96, 192), max_batch=4, workload="graph",
                       filter_k=10, cache_capacity=0, **SEED_KW)
    rs_short = simulate.simulate_reads(ref, n_reads=8, read_len=90,
                                       profile=simulate.ILLUMINA, seed=14)
    rs_long = simulate.simulate_reads(ref, n_reads=8, read_len=180,
                                      profile=simulate.ILLUMINA, seed=15)
    with ServeEngine(egi, cfg) as eng:
        eng.map_all(list(rs_short.reads) + list(rs_long.reads))
        first = dict(eng.trace_counts)
        # both caps traced their prefilter + align exactly once, plus at
        # least one tile-count rung each
        for cap in (96, 192):
            assert first.get((cap, "prefilter")) == 1
            assert first.get((cap, "align")) == 1
            rungs = [k for k in first if k[0] == cap
                     and isinstance(k[1], int)]
            assert rungs, f"no candidate-stage rung traced for cap {cap}"
        assert all(v == 1 for v in first.values()), first
        # repeat traffic of the same shape: no retraces, no new rungs
        eng.map_all(list(rs_short.reads) + list(rs_long.reads))
        assert eng.trace_counts == first
    assert {k[1] for k in eng._executors} == {"graph"}


def test_engine_graph_prefilter_metrics(graph_setup):
    """Graph flushes export the screen/occupancy counters."""
    ref, _, gidx = graph_setup
    egi = gindex.EpochedGraphIndex(gidx)
    cfg = EngineConfig(buckets=(128,), max_batch=4, workload="graph",
                       filter_k=10, **SEED_KW)
    rs = simulate.simulate_reads(ref, n_reads=8, read_len=100,
                                 profile=simulate.ILLUMINA, seed=16)
    with ServeEngine(egi, cfg) as eng:
        eng.map_all(list(rs.reads))
        snap = eng.metrics.snapshot()
    assert snap["graph_candidate_slots"] > 0
    assert snap["graph_tiles_kept"] <= snap["graph_tiles_live"]
    assert snap["graph_dc_rows"] <= snap["graph_dc_rows_dense"]


# ------------------------------------------------------ q-gram primitives --
def test_qgram_bloom_has_no_false_negatives():
    """Every q-gram actually present in the indexed text is confirmed
    (Bloom filters have one-sided error only)."""
    rng = np.random.default_rng(7)
    text = jnp.asarray(rng.integers(0, 4, 300).astype(np.int8))
    bloom = qfilter.qgram_bloom(text, 300)
    codes = qfilter.qgram_codes(text)
    pos_ok = jnp.ones(codes.shape, bool)
    hits = qfilter.qgram_hits(codes, pos_ok, bloom)
    assert int(hits) == codes.shape[0]


def test_qgram_min_hits_bound():
    """The q-gram lemma threshold: m-q+1 - q·k, minus graph slack."""
    q = qfilter.QGRAM_Q
    assert int(qfilter.qgram_min_hits(93, 4, 0)) == 93 - q * 4
    assert int(qfilter.qgram_min_hits(93, 4, 10)) == 93 - q * 4 - 10
    # non-positive bound ⇒ cannot prune (any hit count passes)
    assert int(qfilter.qgram_min_hits(10, 12, 0)) <= 0
