"""repro.shard: layout, partitioning, epoch vectors, failover, engine."""
import numpy as np
import pytest

from repro import shard
from repro.core import minimizer_index
from repro.core.genasm import GenASMConfig
from repro.genomics import encode, io, simulate
from repro.serve import EngineConfig, ResultCache, ServeEngine

W, K = 8, 12
CFG = GenASMConfig()
MAP_KW = dict(cfg=CFG, p_cap=128, filter_bits=128, filter_k=12,
              shard_candidates=4, backend="lax")


@pytest.fixture(scope="module")
def ref():
    return simulate.random_reference(12_000, seed=5)


@pytest.fixture(scope="module")
def epi(ref):
    return minimizer_index.build_epoched_index(ref, w=W, k=K)


@pytest.fixture(scope="module")
def reads(ref):
    return simulate.simulate_reads(ref, n_reads=16, read_len=100,
                                   profile=simulate.ILLUMINA, seed=6)


def test_shard_mesh_never_folds_shards_onto_one_accelerator(monkeypatch):
    """Too few devices: the CPU test path gets no mesh (stacked vmap);
    an accelerator raises instead of running every shard on one chip."""
    import jax

    from repro.dist import sharding

    n = jax.device_count() + 1
    assert sharding.shard_mesh(1) is None
    assert sharding.shard_mesh(n) is None  # CPU: stacked-vmap fallback
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=f"num_shards={n} needs {n}"):
        sharding.shard_mesh(n)


def test_plan_layout_bounds_and_slices():
    lay = shard.plan_layout(1000, 4, halo=100)
    assert lay.bounds == (0, 250, 500, 750, 1000)
    assert lay.core(1) == (250, 500)
    assert lay.slice_range(0) == (0, 350)  # left halo clipped at 0
    assert lay.slice_range(3) == (650, 1000)  # right halo clipped at L
    assert lay.shard_of(0) == 0 and lay.shard_of(499) == 1
    with pytest.raises(ValueError):
        shard.plan_layout(1000, 0)
    with pytest.raises(ValueError):
        shard.plan_layout(3, 8)  # empty core ranges


def test_partition_preserves_bytes_and_table(ref, epi):
    esi = shard.from_epoched(epi, 3)
    sharded = esi.index
    a = sharded.arrays
    g_hash = np.asarray(epi.index.hashes)
    g_pos = np.asarray(epi.index.positions)
    seen = 0
    for i in range(3):
        slo, shi = sharded.layout.slice_range(i)
        row = np.asarray(a.refs[i])
        assert (row[: shi - slo] == ref[slo:shi]).all()
        assert int(a.offsets[i]) == slo
        lo, hi = sharded.layout.core(i)
        m = (g_pos >= lo) & (g_pos < hi)
        got_h = np.asarray(a.hashes[i])[: m.sum()]
        got_p = np.asarray(a.positions[i])[: m.sum()]
        # global table rows, filtered by core ownership, order preserved
        assert (got_h == g_hash[m]).all() and (got_p == g_pos[m]).all()
        seen += int(m.sum())
    assert seen == len(g_pos)  # cores partition every entry exactly once


def test_required_halo_validation(ref, epi):
    esi = shard.from_epoched(epi, 2, halo=64)  # far too small
    with pytest.raises(ValueError, match="halo"):
        shard.validate_geometry(esi.index, p_cap=128, filter_bits=128,
                                filter_k=12, t_cap=128 + 2 * CFG.w)
    need = shard.required_halo(p_cap=128, filter_bits=128, filter_k=12,
                               t_cap=128 + 2 * CFG.w)
    ok = shard.from_epoched(epi, 2, halo=need)
    shard.validate_geometry(ok.index, p_cap=128, filter_bits=128,
                            filter_k=12, t_cap=128 + 2 * CFG.w)


def test_epoch_vector_tokens(ref, epi):
    esi = shard.from_epoched(epi, 2)
    _, t0 = esi.current()
    assert t0[1] == (0, 0)
    t1 = esi.refresh_shard(1)
    assert t1[1] == (0, 1) and t1 != t0
    t2 = esi.refresh(ref)
    assert t2[1] == (1, 2)
    assert len({t0, t1, t2}) == 3  # every refresh is a distinct cache key


def test_epoch_vector_prevents_scalar_collision(ref, epi):
    """Regression: keying the result cache on a scalar shard-local epoch
    aliases distinct shard states.  After refresh_shard(0) vs
    refresh_shard(1), both states have max(epochs) == sum(epochs) == 1 —
    a scalar key would serve state-A results for state-B lookups.  The
    (layout, epoch-vector) token keeps them distinct."""
    a = shard.from_epoched(epi, 2)
    b = shard.from_epoched(epi, 2)
    a.refresh_shard(0)
    b.refresh_shard(1)
    tok_a, tok_b = a.epoch_token(), b.epoch_token()
    assert sum(tok_a[1]) == sum(tok_b[1]) == 1  # scalar summaries collide
    assert max(tok_a[1]) == max(tok_b[1]) == 1
    assert tok_a != tok_b  # ...but the vector token does not
    cache = ResultCache(capacity=8)
    read = np.zeros(8, np.int8)
    cache.put(read, tok_a, "mapped-against-A")
    assert cache.get(read, tok_b) is None  # no cross-state hit
    assert cache.get(read, tok_a) == "mapped-against-A"


def test_refresh_shard_rematerializes_identically(ref, epi, reads):
    arr, lens = encode.batch_reads(list(reads.reads), 128)
    esi = shard.from_epoched(epi, 2)
    before = shard.map_batch_sharded(esi.index, arr, lens, **MAP_KW)
    esi.refresh_shard(0)
    after = shard.map_batch_sharded(esi.index, arr, lens, **MAP_KW)
    for f_b, f_a in zip(before, after):
        assert (np.asarray(f_b) == np.asarray(f_a)).all()


def test_failover_requeues_lost_shard(ref, epi, reads):
    arr, lens = encode.batch_reads(list(reads.reads), 128)
    esi = shard.from_epoched(epi, 3)
    clean = shard.map_batch_with_failover(esi, arr, lens, **MAP_KW)

    failures = []

    def lose_shard_once(i, attempt):
        if i == 1 and attempt == 1:
            failures.append(i)
            raise RuntimeError("simulated device loss")

    esi2 = shard.from_epoched(epi, 3)
    res = shard.map_batch_with_failover(esi2, arr, lens,
                                        fault_hook=lose_shard_once, **MAP_KW)
    assert failures == [1]  # the fault fired
    assert esi2.epochs == [0, 1, 0]  # lost shard re-materialized, epoch bumped
    for f_c, f_r in zip(clean, res):  # no read dropped, bytes unchanged
        assert (np.asarray(f_c) == np.asarray(f_r)).all()
    assert (res.position >= -1).all() and (res.position >= 0).sum() >= 12


def test_failover_gives_up_after_max_attempts(ref, epi, reads):
    arr, lens = encode.batch_reads(list(reads.reads[:4]), 128)
    esi = shard.from_epoched(epi, 2)

    def always_lose(i, attempt):
        if i == 0:
            raise RuntimeError("persistent loss")

    with pytest.raises(RuntimeError, match="failed 2 times"):
        shard.map_batch_with_failover(esi, arr, lens, max_attempts=2,
                                      fault_hook=always_lose, **MAP_KW)


def _paf_rows(res, lens, ref_len):
    rows = []
    for i in range(len(lens)):
        L = int(lens[i])
        rows.append({
            "qname": f"read{i}", "qlen": L, "qstart": 0, "qend": L,
            "strand": "+", "tname": "ref", "tlen": ref_len,
            "tstart": int(res.position[i]),
            "tend": int(res.position[i]) + L,
            "nmatch": L - int(res.distance[i]), "alnlen": L, "mapq": 60,
            "cigar": io.cigar_string(np.asarray(res.ops)[i],
                                     int(res.n_ops[i])),
        })
    return rows


def test_failover_align_chunk_requeues_in_pipelined_mode(ref, epi, reads,
                                                         tmp_path):
    """A shard lost *between merge and align* (the window the pipelined
    path opens) re-queues its align chunk; the re-assembled PAF bytes
    are identical to a clean full-batch run."""
    arr, lens = encode.batch_reads(list(reads.reads), 128)
    esi = shard.from_epoched(epi, 3)
    clean = shard.map_batch_with_failover(esi, arr, lens, **MAP_KW)

    failures = []

    def lose_between_merge_and_align(i, attempt):
        if i == 1 and attempt == 1:
            failures.append(i)
            raise RuntimeError("simulated device loss mid-pipeline")

    esi2 = shard.from_epoched(epi, 3)
    res = shard.map_batch_with_failover(
        esi2, arr, lens, pipelined=True,
        align_fault_hook=lose_between_merge_and_align, **MAP_KW)
    assert failures == [1]  # the fault fired after the device merge
    assert esi2.epochs == [0, 1, 0]  # lost shard re-materialized
    p_clean, p_fault = tmp_path / "clean.paf", tmp_path / "fault.paf"
    io.write_paf(p_clean, _paf_rows(clean, lens, len(ref)))
    io.write_paf(p_fault, _paf_rows(res, lens, len(ref)))
    assert p_clean.read_bytes() == p_fault.read_bytes()
    # and the failover driver's output equals the one-program device
    # merge path (same packed-key reduction, different launch structure)
    direct = shard.map_batch_sharded(esi.index, arr, lens, **MAP_KW)
    for f_c, f_d in zip(clean, direct):
        assert (np.asarray(f_c) == np.asarray(f_d)).all()


def test_failover_graph_faults_yield_identical_gaf(ref, reads, tmp_path):
    """Graph failover: a screen-phase loss AND an align-chunk loss in the
    same batch still yield byte-identical GAF output."""
    from repro.graph import index as graph_index

    variants = simulate.simulate_variants(ref, n_snp=20, n_ins=10,
                                          n_del=10, seed=7)
    gidx = graph_index.build_graph_index(ref, variants, w=W, k=K,
                                         window=128 + 2 * CFG.w)
    arr, lens = encode.batch_reads(list(reads.reads), 128)
    kw = dict(cfg=CFG, p_cap=128, filter_bits=128, filter_k=12,
              shard_candidates=4, backend="graph_lax")

    esi = shard.from_epoched_graph(gidx, 3)
    clean = shard.map_batch_with_failover_graph(esi, arr, lens, **kw)

    failures = []

    def lose_screen(i, attempt):
        if i == 0 and attempt == 1:
            failures.append(("screen", i))
            raise RuntimeError("simulated loss in screen")

    def lose_align_chunk(i, attempt):
        if i == 1 and attempt == 1:
            failures.append(("align", i))
            raise RuntimeError("simulated loss between merge and align")

    esi2 = shard.from_epoched_graph(gidx, 3)
    res = shard.map_batch_with_failover_graph(
        esi2, arr, lens, pipelined=True, fault_hook=lose_screen,
        align_fault_hook=lose_align_chunk, **kw)
    assert failures == [("screen", 0), ("align", 1)]
    assert esi2.epochs == [1, 1, 0]

    def gaf_rows(r):
        rows = []
        for i in range(len(lens)):
            L = int(lens[i])
            pstr, plen = io.gaf_path(np.asarray(r.path)[i])
            rows.append({
                "qname": f"read{i}", "qlen": L, "qstart": 0, "qend": L,
                "strand": "+", "path": pstr, "plen": plen, "pstart": 0,
                "pend": plen, "nmatch": L - int(r.distance[i]),
                "alnlen": int(r.n_ops[i]), "mapq": 60,
                "cigar": io.cigar_string(np.asarray(r.ops)[i],
                                         int(r.n_ops[i])),
            })
        return rows

    p_clean, p_fault = tmp_path / "clean.gaf", tmp_path / "fault.gaf"
    io.write_gaf(p_clean, gaf_rows(clean))
    io.write_gaf(p_fault, gaf_rows(res))
    assert p_clean.read_bytes() == p_fault.read_bytes()


def test_engine_pipelined_sharded_matches_single(epi, reads):
    """Device merge + mesh-split align + double-buffered flushes change
    dispatch structure only — results stay bit-identical."""
    base = dict(buckets=(128,), max_batch=4, filter_k=12,
                minimizer_w=W, minimizer_k=K, align_backend="lax")
    with ServeEngine(epi, EngineConfig(**base)) as eng1:
        r1 = eng1.map_all(list(reads.reads))
    with ServeEngine(epi, EngineConfig(num_shards=2, align_sharded=True,
                                       pipelined=True, **base)) as eng2:
        r2 = eng2.map_all(list(reads.reads))  # >=4 flushes: pending overlaps
        assert eng2.metrics.counter("batches_flushed").value >= 4
    for a, b in zip(r1, r2):
        assert (a.position, a.distance, a.n_ops) == \
            (b.position, b.distance, b.n_ops)
        assert (a.ops == b.ops).all()


def test_engine_sharded_matches_single(epi, reads):
    base = dict(buckets=(128,), max_batch=4, filter_k=12,
                minimizer_w=W, minimizer_k=K, align_backend="lax")
    with ServeEngine(epi, EngineConfig(**base)) as eng1:
        r1 = eng1.map_all(list(reads.reads))
    with ServeEngine(epi, EngineConfig(num_shards=2, **base)) as eng2:
        r2 = eng2.map_all(list(reads.reads))
        # one scatter + one align trace for the single bucket cap
        assert eng2.trace_counts == {(128, "scatter"): 1,
                                     (128, "align"): 1}
        # second pass is served from the result cache under the token key
        r2c = eng2.map_all(list(reads.reads))
        assert all(r.cached for r in r2c)
        assert eng2.trace_counts == {(128, "scatter"): 1,
                                     (128, "align"): 1}
    for a, b in zip(r1, r2):
        assert (a.position, a.distance, a.n_ops) == \
            (b.position, b.distance, b.n_ops)
        assert (a.ops == b.ops).all()


def test_engine_rejects_mismatched_shard_count(epi):
    esi = shard.from_epoched(epi, 3)
    cfg = EngineConfig(buckets=(128,), num_shards=2, filter_k=12,
                       minimizer_w=W, minimizer_k=K)
    with pytest.raises(ValueError, match="sharded 3 ways"):
        ServeEngine(esi, cfg)
