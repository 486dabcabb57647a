"""repro.graph index + core graph construction: tiles, epochs, mapper."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import filter as qfilter
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


def _loop_build_graph(ref, variants):
    """The position-by-position build `build_graph` replaced: its oracle."""
    L = len(ref)
    bases, backbone, src, dst = [], [], [], []
    node_of_backbone = np.full(L, -1, np.int64)
    by_pos = {}
    for v in variants:
        by_pos.setdefault(v.pos, []).append(v)
    prev_tails, pending_del = [], {}
    for p in range(L):
        nid = len(bases)
        bases.append(int(ref[p]))
        backbone.append(p)
        node_of_backbone[p] = nid
        preds = prev_tails + pending_del.pop(p, [])
        for t in preds:
            src.append(t)
            dst.append(nid)
        prev_tails = [nid]
        for v in by_pos.get(p, []):
            if v.kind == "snp":
                if not v.alt:
                    raise ValueError(f"snp at {p} needs a non-empty alt")
                prev = -1
                for j, ab in enumerate(v.alt):
                    alt_id = len(bases)
                    bases.append(int(ab))
                    backbone.append(-1)
                    for a in (preds if j == 0 else [prev]):
                        src.append(a)
                        dst.append(alt_id)
                    prev = alt_id
                prev_tails.append(prev)
            elif v.kind == "ins":
                prev = nid
                for ab in v.alt:
                    alt_id = len(bases)
                    bases.append(int(ab))
                    backbone.append(-1)
                    src.append(prev)
                    dst.append(alt_id)
                    prev = alt_id
                prev_tails.append(prev)
            elif v.kind == "del":
                tgt = p + v.span + 1
                if tgt >= L:
                    raise ValueError(
                        f"del at {p} (span {v.span}) lands at backbone "
                        f"{tgt}, past the reference end {L}; trim the "
                        f"variant or extend the reference")
                pending_del.setdefault(tgt, []).append(nid)
            else:
                raise ValueError(v.kind)
    succ = np.zeros(len(bases), np.uint32)
    if src:
        a, b = np.asarray(src, np.int64), np.asarray(dst, np.int64)
        hop = b - a - 1
        if hop.max() >= cgraph.HOP_LIMIT:
            w = int(hop.argmax())
            raise ValueError(
                f"edge {int(a[w])}->{int(b[w])} hop {int(hop[w]) + 1} "
                f"exceeds HOP_LIMIT={cgraph.HOP_LIMIT}; re-chunk the graph")
        np.bitwise_or.at(succ, a, np.uint32(1) << hop.astype(np.uint32))
    return cgraph.GenomeGraph(np.array(bases, np.int8), succ,
                              np.array(backbone, np.int32),
                              node_of_backbone.astype(np.int32))


INDEL_LENGTHS = np.arange(1, 16)  # the 1000 Genomes table's 1-15 bp
INDEL_WEIGHTS = np.array([46, 15, 7, 8, 4, 3, 2, 3, 2, 2, 1, 2, 1, 2, 2],
                         float)


def _variant_mix(seed, L, *, every=35, snp_share=46 / 48, pitch=17):
    """Variants at population density: sites about ``every`` bp apart on
    a ``pitch`` grid (so that no 15 bp deletion reaches the next site),
    mostly SNPs with 1-3 base alts, indels drawn from a 1-15 bp table;
    beside some SNPs a second SNP at the same position or the next, and a
    deletion at the last legal position."""
    rng = np.random.default_rng(seed)
    sites = np.unique(rng.integers(1, (L - 20) // pitch,
                                   size=L // every)) * pitch
    w = INDEL_WEIGHTS / INDEL_WEIGHTS.sum()
    out = []
    for p in (int(x) for x in sites):
        r = rng.random()
        n = int(rng.choice(INDEL_LENGTHS, p=w))
        if r < snp_share:
            size = 1 + int(rng.random() < 0.1) * int(rng.integers(1, 3))
            out.append(cgraph.Variant(p, "snp", tuple(
                int(x) for x in rng.integers(0, 4, size=size))))
            if rng.random() < 0.2:  # a second allele here, or next door
                out.append(cgraph.Variant(p + int(rng.integers(0, 2)), "snp",
                                          (int(rng.integers(0, 4)),)))
        elif r < (1 + snp_share) / 2:
            out.append(cgraph.Variant(p, "ins", tuple(
                int(x) for x in rng.integers(0, 4, size=n))))
        else:
            out.append(cgraph.Variant(p, "del", span=n))
    out.append(cgraph.Variant(L - 3, "del", span=1))  # lands at L - 1
    order = rng.permutation(len(out))  # list order is not position order
    return [out[i] for i in order]


@pytest.mark.parametrize("seed", [0, 1, 2, 2 ** 31 + 5])
def test_build_graph_matches_loop(seed):
    """The array build gives the position-by-position loop's graph: node
    order, hopBits, backbone and node_of_backbone, at population density
    with 1-15 bp indels, multi-base SNP alts and adjacent sites."""
    ref = simulate.random_reference(20_000, seed=seed % 1000)
    variants = _variant_mix(seed, len(ref))
    want = _loop_build_graph(ref, variants)
    got = cgraph.build_graph(ref, variants)
    for f in ("bases", "succ_bits", "backbone", "node_of_backbone"):
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype, f
        np.testing.assert_array_equal(a, b, err_msg=f)
    # and dense edge cases: every kind at one position, a graph end
    ref = np.tile(np.arange(4, dtype=np.int8), 8)
    dense = [cgraph.Variant(3, "ins", (1, 2)), cgraph.Variant(3, "snp", (0,)),
             cgraph.Variant(3, "del", span=2), cgraph.Variant(4, "snp", (2,)),
             cgraph.Variant(6, "snp", (1, 1, 1)), cgraph.Variant(31, "ins",
                                                                 (3,)),
             cgraph.Variant(0, "snp", (3,)), cgraph.Variant(2, "ins", ()),
             cgraph.Variant(40, "snp", (1,))]
    want, got = _loop_build_graph(ref, dense), cgraph.build_graph(ref, dense)
    for f in ("bases", "succ_bits", "backbone", "node_of_backbone"):
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f),
                                      err_msg=f)


@pytest.mark.parametrize("bad", [
    [cgraph.Variant(27, "del", span=2)],
    [cgraph.Variant(5, "snp", ())],
    [cgraph.Variant(5, "del", span=20)],
    [cgraph.Variant(5, "inv", span=2)],
    [cgraph.Variant(4, "ins", tuple([1] * 14)), cgraph.Variant(4, "snp", (2,)),
     cgraph.Variant(3, "snp", (1,))],
    [cgraph.Variant(9, "snp", ()), cgraph.Variant(7, "del", span=40),
     cgraph.Variant(7, "snp", ())],
    [cgraph.Variant(2, "ins", tuple([0] * 15)), cgraph.Variant(12, "del",
                                                               span=16)],
])
def test_build_graph_raises_as_loop(bad):
    """The same ValueError, message and all, as the loop raised first."""
    ref = np.zeros(30, np.int8)
    with pytest.raises(ValueError) as want:
        _loop_build_graph(ref, bad)
    with pytest.raises(ValueError) as got:
        cgraph.build_graph(ref, bad)
    assert str(got.value) == str(want.value)


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


# ------------------------------------------------------------ one store ---
def _gather(idx, starts, length):
    return np.asarray(gindex.gather_windows(
        idx.arrays.gtext, jnp.asarray(starts), length, n_nodes=idx.n_nodes))


def _packed_extract(g, start, length):
    bases, succ = cgraph.extract_subgraph(g, start, length)
    return np.asarray(windowed.pack_graph_text(jnp.asarray(bases),
                                               jnp.asarray(succ)))


def test_tiles_match_extract_subgraph():
    """Every window gathered from the one stored text is extract_subgraph
    at its start and length — one masking rule — at each tile start, for
    the screen's tile length and each bucket rung's gather length."""
    ref = simulate.random_reference(900, seed=3)
    variants = simulate.simulate_variants(ref, n_snp=6, n_ins=3, n_del=3,
                                          seed=4)
    g = cgraph.build_graph(ref, variants)
    cfg = EngineConfig(workload="graph")
    t_caps = [cap + 2 * cfg.genasm.w for cap in cfg.buckets]
    idx = gindex.build_graph_index(ref, variants, w=8, k=12,
                                   window=max(t_caps), tile_stride=64)
    assert idx.arrays.gtext.shape == (g.n_nodes,)  # each node once
    starts = np.array([0, 1, idx.n_tiles // 2, idx.n_tiles - 1]) \
        * idx.tile_stride
    rungs = [t_cap + idx.tile_stride + idx.margin for t_cap in t_caps]
    assert rungs[-1] == idx.tile_len
    for length in rungs:
        got = _gather(idx, starts, length)
        for s, row in zip(starts, got):
            np.testing.assert_array_equal(
                row, _packed_extract(g, int(s), length),
                err_msg=f"window at {s}, length {length}")


def test_chunked_tile_build_matches_one_pass(monkeypatch):
    """The tile screen built a chunk of tiles at a time, from per-block
    Blooms, equals the one-pass build and each tile's own Bloom and slack
    over its extract_subgraph window."""
    ref = simulate.random_reference(12_000, seed=4)
    vs = simulate.simulate_variants(ref, n_snp=30, n_ins=15, n_del=15,
                                    seed=5)
    g = cgraph.build_graph(ref, vs)
    gtext = windowed.pack_graph_text(jnp.asarray(g.bases),
                                     jnp.asarray(g.succ_bits))
    for tile_len in (512, 480, 455):  # q-grams ending mid-block or not
        one = gindex._build_screen(gtext, tile_len=tile_len, tile_stride=64)
        monkeypatch.setattr(gindex, "TILE_CHUNK", 37)
        chunked = gindex._build_screen(gtext, tile_len=tile_len,
                                       tile_stride=64)
        monkeypatch.undo()
        for a, b in zip(one, chunked):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        bloom, slack = (np.asarray(a) for a in one)
        assert len(bloom) == -(-g.n_nodes // 64)
        for c in (0, 1, len(bloom) // 2, len(bloom) - 3, len(bloom) - 1):
            bases, succ = cgraph.extract_subgraph(g, c * 64, tile_len)
            valid = min(tile_len, g.n_nodes - c * 64)
            want = qfilter.qgram_bloom(jnp.asarray(bases), valid)
            np.testing.assert_array_equal(bloom[c], np.asarray(want),
                                          err_msg=f"tile {c} Bloom")
            hops = sum(bin(int(x) >> 1).count("1") for x in succ[:valid])
            assert slack[c] == (qfilter.QGRAM_Q - 1) * hops, f"tile {c}"


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
    _, succ = windowed.unpack_graph_text(idx.arrays.gtext)
    succ = np.asarray(succ)
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


def test_engine_holds_graph_index_to_its_budget():
    ref = simulate.random_reference(3000, seed=11)
    variants = simulate.simulate_variants(ref, n_snp=8, n_ins=4, n_del=4,
                                          seed=12)
    egi = gindex.build_epoched_graph_index(
        ref, variants, w=8, k=12, window=96 + 2 * 64)
    nbytes = sum(int(a.nbytes) for a in egi.index.arrays)
    kw = dict(buckets=(96,), workload="graph", minimizer_w=8, minimizer_k=12)
    with ServeEngine(egi, EngineConfig(graph_index_max_bytes=nbytes,
                                       **kw)) as eng:
        gauges = eng.metrics.snapshot()
    assert gauges["graph_index_device_bytes"] == nbytes
    assert gauges["graph_nodes"] == egi.index.n_nodes
    with pytest.raises(ValueError, match=f"{nbytes} bytes.*max_bytes"):
        ServeEngine(egi, EngineConfig(graph_index_max_bytes=nbytes - 1,
                                      **kw))
    with pytest.raises(ValueError, match="graph_index_max_bytes"):
        EngineConfig(graph_index_max_bytes=0, **kw)


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
