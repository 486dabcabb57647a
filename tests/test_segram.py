"""SeGraM: minimizers, graph construction, BitAlign vs graph-DP oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import oracle
from repro.core.segram import bitalign, graph, minimizer, segram
from repro.genomics import encode, simulate

from conftest import mutate_seq


def test_minimizers_cover_windows(rng):
    seq = rng.integers(0, 4, size=300).astype(np.int8)
    is_min, h = minimizer.minimizers(jnp.asarray(seq), w=8, k=12)
    is_min = np.asarray(is_min)
    h = np.asarray(h)
    # every window of 8 k-mers must contain at least one sampled minimizer
    n_k = len(h)
    for s in range(0, n_k - 8 + 1, 8):
        assert is_min[s: s + 8].any()


def test_minimizer_index_roundtrip(rng):
    ref = rng.integers(0, 4, size=2000).astype(np.int8)
    idx = minimizer.build_index(ref, w=8, k=12)
    # query with an exact fragment: true diagonal must be a candidate
    start = 700
    read = ref[start: start + 120]
    starts, votes = minimizer.seed_candidates(
        jnp.asarray(read), jnp.asarray(idx.hashes), jnp.asarray(idx.positions),
        w=8, k=12)
    starts = np.asarray(starts)[np.asarray(votes) > 0]
    assert any(abs(int(s) - start) <= 32 for s in starts)


def test_linear_graph_equals_linear_bitap(rng):
    ref = rng.integers(0, 4, size=96).astype(np.int8)
    g = graph.linear_graph(ref)
    m = 30
    pat = mutate_seq(ref[10: 10 + m], 2, 1, 1, rng)
    pbuf = np.full((64,), 4, np.int8)
    pbuf[: len(pat)] = pat
    dists, _ = bitalign.bitalign_dc(jnp.asarray(g.bases), jnp.asarray(g.succ_bits),
                                    jnp.asarray(pbuf), jnp.int32(len(pat)),
                                    m_bits=64, k=8)
    got = int(np.asarray(dists).min())
    want = min(min(oracle.levenshtein_prefix(pat, ref[i:]) for i in range(96)), 9)
    assert got == want


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_property_bitalign_matches_graph_dp(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2 ** 31)))
    ref = rng.integers(0, 4, size=70).astype(np.int8)
    variants = simulate.simulate_variants(ref, n_snp=3, n_ins=2, n_del=1,
                                          seed=int(rng.integers(0, 999)))
    g = graph.build_graph(ref, variants)
    m = data.draw(st.integers(10, 32))
    start = data.draw(st.integers(0, 30))
    pat = mutate_seq(ref[start: start + m], data.draw(st.integers(0, 2)),
                     data.draw(st.integers(0, 1)), data.draw(st.integers(0, 1)),
                     rng)
    pbuf = np.full((64,), 4, np.int8)
    pbuf[: len(pat)] = pat
    dists, _ = bitalign.bitalign_dc(jnp.asarray(g.bases), jnp.asarray(g.succ_bits),
                                    jnp.asarray(pbuf), jnp.int32(len(pat)),
                                    m_bits=64, k=10)
    got = int(np.asarray(dists).min())
    want = min(oracle.graph_edit_distance(pat, g.bases, graph.predecessors(g)), 11)
    assert got == want


def test_bitalign_traceback_valid_path(rng):
    ref = np.tile(np.arange(4, dtype=np.int8), 25)
    variants = [graph.Variant(10, "snp", (3,)), graph.Variant(30, "del", span=2),
                graph.Variant(50, "ins", (2, 2))]
    g = graph.build_graph(ref, variants)
    pat = np.asarray(g.bases[5:35]).copy()
    pbuf = np.full((64,), 4, np.int8)
    pbuf[: len(pat)] = pat
    res = bitalign.bitalign(jnp.asarray(g.bases), jnp.asarray(g.succ_bits),
                            jnp.asarray(pbuf), jnp.int32(len(pat)), m_bits=64,
                            k=10)
    assert not bool(res["failed"])
    ops = np.asarray(res["ops"])
    nodes = np.asarray(res["nodes"])
    pi, edits, last = 0, 0, -1
    for s in range(int(res["n_ops"])):
        op, nd = int(ops[s]), int(nodes[s])
        if op in (0, 1, 3):
            assert nd > last
            last = nd
        if op == 0:
            assert g.bases[nd] == pat[pi]
            pi += 1
        elif op in (1, 2):
            pi += 1
            edits += 1
        elif op == 3:
            edits += 1
    assert pi == len(pat)
    assert edits == int(res["distance"])


def test_segram_end_to_end_maps_reads(rng):
    ref = simulate.random_reference(3000, seed=42)
    variants = simulate.simulate_variants(ref, n_snp=10, n_ins=4, n_del=4, seed=7)
    g = graph.build_graph(ref, variants)
    idx = segram.preprocess(ref, g, w=8, k=12)
    rs = simulate.simulate_reads(ref, n_reads=6, read_len=100,
                                 profile=simulate.ILLUMINA, seed=8)
    reads, lens = encode.batch_reads(rs.reads, 128)
    out = segram.map_batch(idx, jnp.asarray(reads), jnp.asarray(lens),
                           m_bits=128, k=16, win_len=192, minimizer_w=8,
                           minimizer_k=12)
    assert int(np.sum(~np.asarray(out["failed"]))) >= 5


@pytest.mark.parametrize("n,chunk", [(5000, 700), (20011, 4096), (300, 64)])
def test_chunked_minimizers_match_one_pass(rng, n, chunk):
    """The index build's chunked device passes give the one-pass bytes."""
    ref = rng.integers(0, 4, n).astype(np.int8)
    ref[rng.integers(0, n, 20)] = 4  # N bases: invalid k-mers
    is_min, h = minimizer.minimizers(jnp.asarray(ref), w=10, k=15)
    c_min, c_h = minimizer.host_minimizers(ref, w=10, k=15, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(is_min), c_min)
    np.testing.assert_array_equal(np.asarray(h), c_h)
