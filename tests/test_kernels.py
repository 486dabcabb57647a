"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("w,k,bt", [(64, 24, 8), (64, 8, 4), (96, 16, 8),
                                    (128, 24, 4)])
def test_genasm_dc_kernel_matches_ref(rng, w, k, bt):
    b = 2 * bt
    texts = rng.integers(0, 5, size=(b, w)).astype(np.int8)
    pats = rng.integers(0, 5, size=(b, w)).astype(np.int8)
    d_k, tb_k = ops.window_dc(jnp.asarray(texts), jnp.asarray(pats), w=w, k=k,
                              block_bt=bt)
    d_r, tb_r = ref.window_dc_batch(jnp.asarray(texts), jnp.asarray(pats), w=w, k=k)
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(tb_k), np.asarray(tb_r))


def test_genasm_dc_kernel_pads_ragged_batch(rng):
    texts = rng.integers(0, 4, size=(5, 64)).astype(np.int8)
    pats = rng.integers(0, 4, size=(5, 64)).astype(np.int8)
    d, tb = ops.window_dc(jnp.asarray(texts), jnp.asarray(pats), block_bt=4)
    assert d.shape == (5,)
    assert tb.shape[0] == 5


@pytest.mark.parametrize("m_bits,mode", [(32, "global"), (64, "global"),
                                         (64, "semiglobal"), (128, "semiglobal")])
def test_myers_kernel_matches_ref(rng, m_bits, mode):
    b, n = 8, 96
    texts = rng.integers(0, 4, size=(b, n)).astype(np.int8)
    pats = np.full((b, m_bits), 4, np.int8)
    lens = rng.integers(4, min(m_bits, 60), size=(b,)).astype(np.int32)
    for i in range(b):
        pats[i, : lens[i]] = rng.integers(0, 4, size=lens[i])
    dk = ops.myers_distance(jnp.asarray(texts), jnp.asarray(pats),
                            jnp.asarray(lens), m_bits=m_bits, mode=mode,
                            block_bt=4)
    dr = ref.myers_distance_batch(jnp.asarray(texts), jnp.asarray(pats),
                                  jnp.asarray(lens), m_bits=m_bits, mode=mode)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))


def test_kernel_wildcards_and_sentinels(rng):
    """Wildcard pattern chars match everything incl. text sentinels."""
    texts = np.full((8, 64), 4, np.int8)  # all-sentinel text
    pats = np.full((8, 64), 4, np.int8)  # all-wildcard pattern
    d, _ = ops.window_dc(jnp.asarray(texts), jnp.asarray(pats), block_bt=8)
    np.testing.assert_array_equal(np.asarray(d), 0)


@pytest.mark.parametrize("w,k,bt", [(64, 24, 8), (64, 16, 4), (96, 16, 8)])
def test_genasm_dc_v2_kernel_matches_ref(rng, w, k, bt):
    b = 2 * bt
    texts = rng.integers(0, 5, size=(b, w)).astype(np.int8)
    pats = rng.integers(0, 5, size=(b, w)).astype(np.int8)
    d_k, r_k = ops.window_dc_v2(jnp.asarray(texts), jnp.asarray(pats), w=w, k=k,
                                block_bt=bt)
    d_r, r_r = ref.window_dc_batch_v2(jnp.asarray(texts), jnp.asarray(pats),
                                      w=w, k=k)
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))


def test_v2_store_is_3x_smaller():
    """The §Perf #8 claim: R-only store ≈ 1/3 of the M/I/D store."""
    w, k, nw = 64, 24, 2
    v1 = w * (k + 1) * 3 * nw * 4
    v2 = (w + 1) * (k + 1) * nw * 4
    assert v1 / v2 > 2.9


@pytest.mark.parametrize("m_bits,k", [(64, 10), (96, 8)])
def test_bitalign_kernel_matches_ref(rng, m_bits, k):
    from repro.core.segram import graph
    from repro.genomics import simulate

    B, N = 8, 80
    bases = np.zeros((B, N), np.int8)
    succ = np.zeros((B, N), np.uint32)
    pats = np.full((B, m_bits), 4, np.int8)
    plens = np.zeros((B,), np.int32)
    for i in range(B):
        refseq = rng.integers(0, 4, size=N - 10).astype(np.int8)
        variants = simulate.simulate_variants(refseq, n_snp=2, n_ins=1,
                                              n_del=1, seed=i)
        g = graph.build_graph(refseq, variants)
        bases[i], succ[i] = graph.extract_subgraph(g, 0, N)
        m = int(rng.integers(10, min(40, m_bits - 2)))
        pats[i, :m] = refseq[:m]
        plens[i] = m
    dk, rk_ = ops.bitalign_dc(jnp.asarray(bases), jnp.asarray(succ),
                              jnp.asarray(pats), jnp.asarray(plens),
                              m_bits=m_bits, k=k, block_bt=4)
    dr, rr = ref.bitalign_dc_batch(jnp.asarray(bases), jnp.asarray(succ),
                                   jnp.asarray(pats), jnp.asarray(plens),
                                   m_bits=m_bits, k=k)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(rk_), np.asarray(rr))


@pytest.mark.parametrize("batch,block_bt", [(1, None), (16, None), (32, 128),
                                            (200, 128), (300, 256)])
def test_batch_tile_is_lane_legal_when_compiled(batch, block_bt):
    """Compiled launches only use whole 128-lane tiles; interpret mode
    keeps the small CPU-friendly tile."""
    from repro.kernels.genasm_dc import LANES, batch_tile

    bt = batch_tile(block_bt, batch, interpret=False)
    assert bt % LANES == 0 and bt >= min(block_bt or 128, batch)
    assert batch_tile(block_bt, batch, interpret=True) == \
        min(block_bt or 128, max(8, batch))


@pytest.mark.parametrize("span", [20, 80])
def test_bitalign_filter_kernel_matches_dc_min(rng, span):
    """The filter kernel's (min, argmin) over the span equals the DC
    kernel's per-node distances reduced the same way."""
    from repro.core.segram import graph
    from repro.genomics import simulate
    from repro.kernels import bitalign

    B, N, m_bits, k = 6, 80, 64, 8
    bases = np.zeros((B, N), np.int8)
    succ = np.zeros((B, N), np.uint32)
    pats = np.full((B, m_bits), 4, np.int8)
    plens = np.zeros((B,), np.int32)
    for i in range(B):
        refseq = rng.integers(0, 4, size=N - 10).astype(np.int8)
        variants = simulate.simulate_variants(refseq, n_snp=2, n_ins=1,
                                              n_del=1, seed=i)
        g = graph.build_graph(refseq, variants)
        bases[i], succ[i] = graph.extract_subgraph(g, 0, N)
        m = int(rng.integers(10, 40))
        s0 = int(rng.integers(0, 20))
        pats[i, :m] = refseq[s0:s0 + m]
        plens[i] = m
    args = [jnp.asarray(x) for x in (bases, succ, pats, plens)]
    dists, _ = ops.bitalign_dc(*args, m_bits=m_bits, k=k, block_bt=4)
    d, off = bitalign.bitalign_filter_batch(*args, m_bits=m_bits, k=k,
                                            span=span, block_bt=4,
                                            interpret=True)
    masked = np.where(np.arange(N) < span, np.asarray(dists), k + 1)
    np.testing.assert_array_equal(np.asarray(d), masked.min(-1))
    np.testing.assert_array_equal(np.asarray(off), masked.argmin(-1))
