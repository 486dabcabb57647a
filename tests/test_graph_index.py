"""repro.graph index + core graph construction: tiles, epochs, mapper."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import oracle
from repro.core.segram import graph as cgraph
from repro.core.segram import segram
from repro.graph import index as gindex
from repro.graph import mapper as gmapper
from repro.graph import windowed
from repro.genomics import encode, simulate
from repro.serve import EngineConfig, ServeEngine


# ----------------------------------------------------- graph construction --
def test_build_graph_multibase_snp_honored():
    """A len-2 snp alt spells a branch: the alt allele aligns at cost 0."""
    ref = np.tile(np.arange(4, dtype=np.int8), 10)
    g = cgraph.build_graph(ref, [cgraph.Variant(10, "snp", (3, 3))])
    assert g.n_nodes == len(ref) + 2
    # the branch replaces ref[10]: ...8,9,[3,3],11,12...
    allele = np.array([ref[8], ref[9], 3, 3, ref[11], ref[12]], np.int8)
    d = oracle.graph_edit_distance(allele, g.bases, cgraph.predecessors(g))
    assert d == 0
    # and the backbone spelling still aligns at cost 0
    d_bb = oracle.graph_edit_distance(ref[8:13], g.bases,
                                      cgraph.predecessors(g))
    assert d_bb == 0


def test_build_graph_snp_branch_shares_predecessors():
    """The first alt node gets exactly its backbone twin's predecessors
    (the list the old implementation re-derived with an O(E) scan)."""
    ref = np.tile(np.arange(4, dtype=np.int8), 10)
    variants = [cgraph.Variant(9, "del", span=2),  # jump lands at 12
                cgraph.Variant(12, "snp", (0,))]
    g = cgraph.build_graph(ref, variants)
    preds = cgraph.predecessors(g)
    nid = int(g.node_of_backbone[12])
    alt = nid + 1  # alt node is emitted right after its twin
    assert g.backbone[alt] == -1
    assert preds[alt] == preds[nid]
    assert len(preds[nid]) == 2  # chain predecessor + deletion jump


def test_build_graph_rejects_bad_variants():
    ref = np.zeros(30, np.int8)
    with pytest.raises(ValueError, match="past the reference end"):
        cgraph.build_graph(ref, [cgraph.Variant(27, "del", span=2)])
    with pytest.raises(ValueError, match="non-empty alt"):
        cgraph.build_graph(ref, [cgraph.Variant(5, "snp", ())])
    with pytest.raises(ValueError, match="HOP_LIMIT"):
        cgraph.build_graph(ref, [cgraph.Variant(5, "del", span=20)])


def test_window_extractors_share_boundary_rule(rng):
    """Host extract_subgraph and device segram._window agree bitwise."""
    ref = simulate.random_reference(600, seed=9)
    variants = simulate.simulate_variants(ref, n_snp=8, n_ins=4, n_del=4,
                                          seed=10)
    g = cgraph.build_graph(ref, variants)
    idx = segram.preprocess(ref, g, w=8, k=12)
    for s in (0, 17, 300, g.n_nodes - 96):
        hb, hs = cgraph.extract_subgraph(g, s, 96)
        db, ds, s0 = segram._window(idx, jnp.int32(s), 96)
        assert int(s0) == s
        np.testing.assert_array_equal(hb, np.asarray(db))
        np.testing.assert_array_equal(hs, np.asarray(ds))


# ----------------------------------------------------------- tiled index ---
def test_tiles_match_extract_subgraph():
    """Every tile is extract_subgraph at its start — one masking rule."""
    ref = simulate.random_reference(900, seed=3)
    variants = simulate.simulate_variants(ref, n_snp=6, n_ins=3, n_del=3,
                                          seed=4)
    g = cgraph.build_graph(ref, variants)
    idx = gindex.build_graph_index(ref, variants, w=8, k=12, window=128,
                                   tile_stride=64)
    tiles = np.asarray(idx.arrays.tile_gtext)
    for c in (0, 1, idx.n_tiles // 2, idx.n_tiles - 1):
        bases, succ = cgraph.extract_subgraph(g, c * idx.tile_stride,
                                              idx.tile_len)
        want = np.asarray(windowed.pack_graph_text(jnp.asarray(bases),
                                                   jnp.asarray(succ)))
        np.testing.assert_array_equal(tiles[c], want, err_msg=f"tile {c}")
        assert int(idx.arrays.tile_valid[c]) == \
            min(idx.tile_len, g.n_nodes - c * idx.tile_stride)


def test_chunked_tile_build_matches_one_pass(monkeypatch):
    """Tiles built a chunk of rows at a time equal the one-pass build."""
    ref = simulate.random_reference(12_000, seed=4)
    vs = simulate.simulate_variants(ref, n_snp=30, n_ins=15, n_del=15,
                                    seed=5)
    g = cgraph.build_graph(ref, vs)
    args = (jnp.asarray(g.bases), jnp.asarray(g.succ_bits))
    one = gindex._build_tiles(*args, tile_len=512, tile_stride=64)
    monkeypatch.setattr(gindex, "TILE_CHUNK", 37)
    chunked = gindex._build_tiles(*args, tile_len=512, tile_stride=64)
    for a, b in zip(one, chunked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_npz_roundtrip(tmp_path):
    ref = simulate.random_reference(800, seed=5)
    variants = simulate.simulate_variants(ref, n_snp=5, n_ins=2, n_del=2,
                                          seed=6)
    idx = gindex.build_graph_index(ref, variants, w=8, k=12, window=128)
    p = tmp_path / "graph_index.npz"
    gindex.save_graph_index(p, idx)
    got = gindex.load_graph_index(p)
    assert (got.tile_len, got.tile_stride) == (idx.tile_len, idx.tile_stride)
    assert (got.minimizer_w, got.minimizer_k) == (8, 12)
    for f in idx.arrays._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(idx.arrays, f)),
            np.asarray(getattr(got.arrays, f)), err_msg=f)
    np.testing.assert_array_equal(idx.ref, got.ref)


def test_epoched_graph_index_refresh_bumps_epoch():
    ref = simulate.random_reference(600, seed=7)
    egi = gindex.build_epoched_graph_index(ref, (), w=8, k=12, window=128)
    idx0, e0 = egi.current()
    variants = simulate.simulate_variants(ref, n_snp=4, n_ins=2, n_del=2,
                                          seed=8)
    assert egi.refresh(ref, variants) == e0 + 1
    idx1, e1 = egi.current()
    assert e1 == e0 + 1
    assert idx1.n_nodes > idx0.n_nodes  # variant nodes landed
    assert idx1.tile_stride == idx0.tile_stride  # build kwargs persisted


# ---------------------------------------------------------------- mapper ---
def test_mapper_chunked_long_reference():
    """A reference ≥ 10× one BitAlign window maps through the tiles."""
    ref = simulate.random_reference(4000, seed=42)  # ~15x a 256-node window
    variants = simulate.simulate_variants(ref, n_snp=12, n_ins=5, n_del=5,
                                          seed=7)
    idx = gindex.build_graph_index(ref, variants, w=8, k=12, window=256)
    assert idx.n_tiles * idx.tile_stride >= 10 * 256
    rs = simulate.simulate_reads(ref, n_reads=12, read_len=100,
                                 profile=simulate.ILLUMINA, seed=8)
    reads, lens = encode.batch_reads(rs.reads, 128)
    out = gmapper.map_batch_index(idx, jnp.asarray(reads), jnp.asarray(lens),
                                  p_cap=128, filter_bits=96, filter_k=12,
                                  backend="graph_lax")
    failed = np.asarray(out.failed)
    pos = np.asarray(out.position)
    ok = (~failed) & (np.abs(pos - rs.true_pos) <= 40)
    assert ok.sum() >= 10
    # paths walk real edges
    succ = np.asarray(idx.arrays.succ_bits)
    for i in np.nonzero(~failed)[0]:
        p = [int(x) for x in np.asarray(out.path[i]) if x >= 0]
        for a, b in zip(p, p[1:]):
            assert (succ[a] >> (b - a - 1)) & 1


def test_mapper_rejects_undersized_tiles():
    ref = simulate.random_reference(600, seed=1)
    idx = gindex.build_graph_index(ref, (), w=8, k=12, window=64)
    with pytest.raises(ValueError, match="rebuild the index"):
        gmapper.map_batch_index(idx, jnp.zeros((2, 128), jnp.int8),
                                jnp.full((2,), 100), p_cap=128)


# ------------------------------------------------------- serving workload --
def test_engine_graph_workload_end_to_end():
    ref = simulate.random_reference(3000, seed=11)
    variants = simulate.simulate_variants(ref, n_snp=8, n_ins=4, n_del=4,
                                          seed=12)
    egi = gindex.build_epoched_graph_index(
        ref, variants, w=8, k=12, window=96 + 2 * 64)
    cfg = EngineConfig(buckets=(96,), max_batch=4, workload="graph",
                       filter_k=10, minimizer_w=8, minimizer_k=12)
    rs = simulate.simulate_reads(ref, n_reads=8, read_len=90,
                                 profile=simulate.ILLUMINA, seed=13)
    with ServeEngine(egi, cfg) as eng:
        assert eng.align_backend in ("graph_lax", "graph_pallas")
        res = eng.map_all(list(rs.reads))
        # graph results carry node paths; cached twins copy them
        ok = [r for r in res if r.position >= 0]
        assert len(ok) >= 6
        assert all(r.path is not None and (r.path >= -1).all() for r in res)
        again = eng.map_all([rs.reads[0]])[0]
        assert again.cached and again.path is not None
        key_workloads = {k[1] for k in eng._executors}
    assert key_workloads == {"graph"}


def test_engine_graph_workload_rejects_linear_index():
    from repro.core import minimizer_index

    ref = simulate.random_reference(1000, seed=2)
    epi = minimizer_index.build_epoched_index(ref, w=8, k=12)
    cfg = EngineConfig(buckets=(96,), workload="graph", minimizer_w=8,
                       minimizer_k=12)
    with pytest.raises(TypeError, match="GraphIndex"):
        ServeEngine(epi, cfg)
    with pytest.raises(ValueError, match="workload"):
        EngineConfig(buckets=(96,), workload="protein")
