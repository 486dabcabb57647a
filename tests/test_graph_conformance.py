"""Graph-backend conformance suite (DESIGN.md §10).

Three anchors, mirroring the linear suite's tiers:

  * **linear-graph equivalence** — a pure-backbone graph pushed through
    ``graph_lax``/``graph_pallas`` must match the linear ``lax`` backend
    *bit for bit* on every ``AlignResult`` field (the graph DC/TB
    generalize the linear recurrences; a chain must collapse exactly);
  * **cross-backend agreement** — on real variant graphs the two graph
    backends agree bitwise (same TB over bitwise-equal DC stores), and
    the filter-pass distances agree between the pure-lax search and the
    Pallas kernel;
  * **oracle tiers** — anchored distances against the
    `graph_edit_distance_anchored` DP oracle: exact for substitution-only
    injections on spelled graph paths, oracle ≤ reported ≤ oracle + 3
    for mixed edits; every emitted path walks real graph edges and every
    M op matches its node base;
  * **the stated bound** — the service's graph path at the benchmark's
    population density (1000 Genomes phase 3: a variant per 35 bp, 1-15
    bp indels, reads from a sample's haplotype), and at sites clustered
    6 bp apart, keeps every answer within the distance bound its
    configuration states of the anchored oracle wherever the bound's
    condition holds, and every CIGAR spells its read along its GAF path.

``REPRO_ALIGN_BACKEND`` (the CI matrix knob) narrows the graph backend
list; pinning a linear backend skips this suite (the linear suite
already runs it through the graph backends' chain packing).
"""
import json
import os
import sys
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

from repro import align
from repro.align import inputs
from repro.core import oracle
from repro.core.genasm import GenASMConfig
from repro.core.segram import graph as cgraph
from repro.graph import windowed
from repro.genomics import simulate

GRAPH_BACKENDS = ("graph_lax", "graph_pallas")
_env = os.environ.get("REPRO_ALIGN_BACKEND")
if _env:
    if _env in GRAPH_BACKENDS:
        GRAPH_BACKENDS = (_env,)
    else:
        pytest.skip(f"matrix pin {_env} is a linear backend; the linear "
                    f"conformance suite covers it", allow_module_level=True)

CFG = GenASMConfig()  # paper geometry: W=64, O=24, k=24
P_CAP, T_CAP = 128, 256
RESULT_FIELDS = ("distance", "ops", "n_ops", "text_consumed", "failed")


def _run(backend, texts, pats, p_lens, t_lens, *, cfg=CFG, p_cap=P_CAP,
         block_bt=4):
    return align.align_batch(
        jnp.asarray(texts), jnp.asarray(pats), jnp.asarray(p_lens),
        jnp.asarray(t_lens), cfg=cfg, backend=backend, p_cap=p_cap,
        block_bt=block_bt)


def _variant_graph(seed, ref_len=400):
    rng = np.random.default_rng(seed)
    ref = simulate.random_reference(ref_len, seed=seed)
    variants = simulate.simulate_variants(
        ref, n_snp=6, n_ins=3, n_del=3, seed=seed + 1)
    return cgraph.build_graph(ref, variants), rng


def _graph_batch(seed, n_pairs=4, *, n_sub=0, n_ins=0, n_del=0):
    """Spelled-path patterns (with injected edits) over one variant graph."""
    g, rng = _variant_graph(seed)
    gtext = np.asarray(
        windowed.pack_graph_text(jnp.asarray(g.bases),
                                 jnp.asarray(g.succ_bits)))
    texts = np.zeros((n_pairs, T_CAP), np.uint32)
    pats = np.full((n_pairs, P_CAP), 4, np.int8)
    p_lens = np.zeros(n_pairs, np.int32)
    t_lens = np.zeros(n_pairs, np.int32)
    starts = []
    for i in range(n_pairs):
        start = int(rng.integers(0, g.n_nodes - T_CAP))
        m = int(rng.integers(40, 90))
        pat = simulate.spell_graph_path(g, start, m, rng)
        for _ in range(n_sub):
            j = int(rng.integers(0, len(pat)))
            pat[j] = (pat[j] + 1 + rng.integers(0, 3)) % 4
        for _ in range(n_ins):
            j = int(rng.integers(0, len(pat)))
            pat = np.insert(pat, j, rng.integers(0, 4))
        for _ in range(n_del):
            j = int(rng.integers(0, len(pat) - 1))
            pat = np.delete(pat, j)
        bases, succ = cgraph.extract_subgraph(g, start, T_CAP)
        texts[i] = np.asarray(windowed.pack_graph_text(
            jnp.asarray(bases), jnp.asarray(succ)))
        pats[i, :len(pat)] = pat
        p_lens[i] = len(pat)
        t_lens[i] = T_CAP
        starts.append(start)
    return g, texts, pats, p_lens, t_lens, starts


def _check_graph_alignment(g, start, pat, p_len, res, i):
    """Path follows succ edges, M bases match, edits == distance."""
    ops = np.asarray(res.ops[i])
    nodes = np.asarray(res.nodes[i])
    n_ops = int(res.n_ops[i])
    pi, edits, prev = 0, 0, None
    for s in range(n_ops):
        op, nd = int(ops[s]), int(nodes[s])
        if op in (0, 1, 3):  # consumes a node
            gn = start + nd
            if prev is not None:
                hop = gn - prev - 1
                assert 0 <= hop < cgraph.HOP_LIMIT, (i, s, prev, gn)
                assert (int(g.succ_bits[prev]) >> hop) & 1, \
                    f"pair {i}: step {s} jumps {prev}->{gn} off-graph"
            prev = gn
        if op == 0:
            assert g.bases[start + nd] == pat[pi], f"pair {i}: M mismatch"
            pi += 1
        elif op in (1, 2):
            pi += 1
            edits += 1
        elif op == 3:
            edits += 1
    assert pi == p_len, f"pair {i}: pattern not fully consumed"
    assert edits == int(res.distance[i]), f"pair {i}: edits != distance"


@pytest.mark.parametrize("backend", GRAPH_BACKENDS)
def test_linear_graph_matches_lax_bitwise(backend, rng):
    """A chain-packed linear text through the graph backends equals the
    linear ``lax`` backend on every output field."""
    pairs = [inputs.mutated_pair(rng, int(rng.integers(16, 120)), n_sub=2,
                                 n_ins=1, n_del=1, t_extra=40)
             for _ in range(6)]
    texts, pats, p_lens, t_lens = inputs.padded_batch(pairs, P_CAP, 192)
    base = _run("lax", texts, pats, p_lens, t_lens)
    packed = np.asarray(windowed.pack_linear_text(jnp.asarray(texts)))
    for sent in (texts, packed):  # int8 auto-pack and explicit uint32
        got = _run(backend, sent, pats, p_lens, t_lens)
        for f in RESULT_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(base, f)), np.asarray(getattr(got, f)),
                err_msg=f"{backend}.{f} diverges from lax "
                        f"(dtype {np.asarray(sent).dtype})")


def test_variant_graph_backends_bit_identical():
    """graph_lax and graph_pallas agree bitwise on variant graphs,
    including the node paths."""
    if len(GRAPH_BACKENDS) < 2:
        pytest.skip("matrix run pins a single backend")
    g, texts, pats, p_lens, t_lens, _ = _graph_batch(
        3, n_sub=2, n_ins=1, n_del=1)
    base = _run("graph_lax", texts, pats, p_lens, t_lens)
    got = _run("graph_pallas", texts, pats, p_lens, t_lens)
    for f in RESULT_FIELDS + ("nodes",):
        np.testing.assert_array_equal(
            np.asarray(getattr(base, f)), np.asarray(getattr(got, f)),
            err_msg=f"graph_pallas.{f} diverges from graph_lax")


@pytest.mark.parametrize("backend", GRAPH_BACKENDS)
def test_subs_only_anchored_distance_exact(backend):
    """Substitution-only injections on spelled paths: distance equals the
    anchored graph-DP oracle; alignments are internally consistent."""
    g, texts, pats, p_lens, t_lens, starts = _graph_batch(11, n_sub=3)
    res = _run(backend, texts, pats, p_lens, t_lens)
    dist = np.asarray(res.distance)
    for i, start in enumerate(starts):
        bases, succ = cgraph.extract_subgraph(g, start, T_CAP)
        sub = cgraph.GenomeGraph(bases, succ, np.zeros(T_CAP, np.int32),
                                 np.zeros(0, np.int32))
        want = oracle.graph_edit_distance_anchored(
            pats[i][: p_lens[i]], bases, cgraph.predecessors(sub), start=0)
        assert dist[i] == want, f"pair {i}: want {want} got {dist[i]}"
        _check_graph_alignment(g, start, pats[i][: p_lens[i]], p_lens[i],
                               res, i)


@pytest.mark.parametrize("backend", GRAPH_BACKENDS)
def test_indel_mix_anchored_within_slack(backend):
    """Mixed edits: oracle ≤ reported ≤ oracle + 3 (the linear suite's
    §4.10.2 slack), alignment always consistent."""
    g, texts, pats, p_lens, t_lens, starts = _graph_batch(
        23, n_sub=2, n_ins=2, n_del=2)
    res = _run(backend, texts, pats, p_lens, t_lens)
    dist = np.asarray(res.distance)
    for i, start in enumerate(starts):
        bases, succ = cgraph.extract_subgraph(g, start, T_CAP)
        sub = cgraph.GenomeGraph(bases, succ, np.zeros(T_CAP, np.int32),
                                 np.zeros(0, np.int32))
        want = oracle.graph_edit_distance_anchored(
            pats[i][: p_lens[i]], bases, cgraph.predecessors(sub), start=0)
        assert dist[i] >= 0, f"pair {i} failed with only 6 edits"
        assert want <= dist[i] <= want + 3, \
            f"pair {i}: oracle {want} got {dist[i]}"
        _check_graph_alignment(g, start, pats[i][: p_lens[i]], p_lens[i],
                               res, i)


def test_filter_search_matches_kernel_bitwise(rng):
    """`windowed.bitalign_search` (the mapper's pure-lax filter) equals
    the Pallas BitAlign DC kernel's per-node distances bitwise."""
    from repro.kernels.bitalign import bitalign_dc_batch

    g, _ = _variant_graph(31)
    win = 160
    b = 8
    bases = np.zeros((b, win), np.int8)
    succ = np.zeros((b, win), np.uint32)
    pats = np.full((b, 64), 4, np.int8)
    p_lens = np.zeros(b, np.int32)
    for i in range(b):
        s = int(rng.integers(0, g.n_nodes - win))
        bases[i], succ[i] = cgraph.extract_subgraph(g, s, win)
        m = int(rng.integers(20, 60))
        pat = simulate.spell_graph_path(g, s + int(rng.integers(0, 30)), m,
                                        rng)
        pats[i, :len(pat)] = pat
        p_lens[i] = len(pat)
    d_lax = jnp.stack([
        windowed.bitalign_search(jnp.asarray(bases[i]), jnp.asarray(succ[i]),
                                 jnp.asarray(pats[i]), jnp.int32(p_lens[i]),
                                 m_bits=64, k=8)
        for i in range(b)])
    d_ker, _ = bitalign_dc_batch(
        jnp.asarray(bases), jnp.asarray(succ), jnp.asarray(pats),
        jnp.asarray(p_lens), m_bits=64, k=8, block_bt=8,
        interpret=align.needs_interpret())
    np.testing.assert_array_equal(np.asarray(d_lax), np.asarray(d_ker))


@pytest.mark.parametrize("backend", GRAPH_BACKENDS)
def test_emit_cigar_false_distances_match(backend):
    """Distances-only mode keeps the AlignResult contract: same distance
    and n_ops as the CIGAR mode, [B, 1] ops, no node path."""
    _, texts, pats, p_lens, t_lens, _ = _graph_batch(7, n_sub=2)
    full = _run(backend, texts, pats, p_lens, t_lens)
    slim = align.align_batch(
        jnp.asarray(texts), jnp.asarray(pats), jnp.asarray(p_lens),
        jnp.asarray(t_lens), cfg=CFG, backend=backend, p_cap=P_CAP,
        emit_cigar=False)
    assert slim.ops.shape == (texts.shape[0], 1)
    assert slim.nodes is None
    np.testing.assert_array_equal(np.asarray(slim.distance),
                                  np.asarray(full.distance))
    np.testing.assert_array_equal(np.asarray(slim.n_ops),
                                  np.asarray(full.n_ops))


# ---------------------------------------------------- the stated bound ---
ROOT = Path(__file__).resolve().parents[1]
POPULATION = ROOT / "bench" / "tests" / "data" / "graph-1kg.json"
STATED = json.loads((ROOT / "bench" / "checks" / "pangenome-sr-batch.json")
                    .read_text())["limits"]["excess_max"]
# the stated bound's condition: the alleles within any 96 bp of backbone
# total at most 24 nodes (a SNP or insertion its allele, a deletion its
# span), and a read has at most 8 deletions
SPAN, ALLELE_NODES, READ_DELETIONS = 96, 24, 8


def _service_answers(config: dict, seed: int, n_reads: int):
    """Map ``n_reads`` 150 bp reads from a sample's haplotype through the
    serve engine over ``config``'s deployment, built as the benchmark
    builds it; return the reads, the answers, the graph's packed text
    unpacked, its node→backbone map, and the variants."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.harness import cell, traffic
    from bench.systems import graph as system
    from repro.serve import ServeEngine

    mix = json.loads((ROOT / "bench" / "traffic" / "sr-batch.json")
                     .read_text())
    rngs = cell.streams(seed)
    data = cell.make_data(config, rngs["data"])
    source = cell.read_source(config, data, rngs["haplotype"])
    reads = traffic.simulate_reads(
        source.seq, np.full(n_reads, 150), mix["error_profile"],
        rngs["window"], source.to_backbone)
    index, ecfg = system.build(config, data, {})
    with ServeEngine(index, ecfg) as engine:
        res = engine.map_all([reads.read(i) for i in range(len(reads))])
    bases, succ = (np.asarray(a) for a in windowed.unpack_graph_text(
        index.index.arrays.gtext))
    backbone = np.asarray(index.index.arrays.backbone)
    return ([reads.read(i) for i in range(len(reads))], res, bases, succ,
            backbone, data.variants)


def _allele_load(variants, n_ref: int) -> np.ndarray:
    """``load[b]``: allele nodes within the ``SPAN`` bp of backbone from
    ``b`` — the most nodes a path over them can skip."""
    nodes = np.zeros(n_ref + SPAN, np.int64)
    np.add.at(nodes, variants.pos,
              np.where(variants.kind == 2, variants.span, variants.alt_len))
    cs = np.concatenate([[0], np.cumsum(nodes)])
    return cs[SPAN:] - cs[:-SPAN]


def _check_answers(reads, res, bases, succ, backbone, load):
    """Every mapped answer's path walks graph edges, its first backbone
    node is the reported position, and its CIGAR spells the read along
    that path with exactly the reported edits; where the bound's condition
    holds, its distance is within the stated bound of the exact anchored optimum at the path's
    first node.  Returns the load of each answer held to the bound."""
    graph = cgraph.GenomeGraph(bases, succ, backbone, None)
    bounded = []
    for i, (read, r) in enumerate(zip(reads, res)):
        if r.position < 0:
            continue
        path = [int(x) for x in r.path[:r.n_ops] if x >= 0]
        on_backbone = backbone[path][backbone[path] >= 0]
        assert on_backbone[0] == r.position, f"read {i}: position"
        for a, b in zip(path, path[1:]):
            hop = b - a - 1
            assert 0 <= hop < cgraph.HOP_LIMIT and (int(succ[a]) >> hop) & 1, \
                f"read {i}: path steps {a}->{b} off the graph"
        err = oracle.check_cigar(r.ops, r.n_ops, read, bases[path], r.distance)
        assert err is None, f"read {i}: {err}"
        worst = int(load[r.position:r.position + len(read)].max())
        deletions = int(np.sum(np.asarray(r.ops[:r.n_ops]) == 3))
        if worst > ALLELE_NODES or deletions > READ_DELETIONS:
            continue
        o, t = path[0], 2 * (len(read) + 8)
        win_b, win_s = cgraph.extract_subgraph(graph, o, t)
        preds = [[] for _ in range(t)]
        for j in range(t):
            for h in range(cgraph.HOP_LIMIT):
                if (int(win_s[j]) >> h) & 1:
                    preds[j + h + 1].append(j)
        want = oracle.graph_edit_distance_anchored(read, win_b, preds)
        assert want <= r.distance <= want + STATED, \
            f"read {i}: distance {r.distance}, optimum {want}"
        bounded.append(worst)
    return bounded


@pytest.mark.parametrize("seed", [2147483677, 17, 5, 2 ** 31 + 101,
                                  4_000_000_007])
def test_service_keeps_stated_bound_at_population_density(seed):
    """Reads from a sample's haplotype, mapped by the serve engine over
    the graph-1kg deployment cut to 200 kbp (as the benchmark builds it):
    every answer is a valid alignment along a graph path, and every one
    of at least 56 of 64 is within the stated bound of the optimum."""
    config = json.loads(POPULATION.read_text())
    reads, res, bases, succ, backbone, variants = _service_answers(
        config, seed, 64)
    load = _allele_load(variants, config["reference_length"])
    bounded = _check_answers(reads, res, bases, succ, backbone, load)
    assert len(bounded) >= 56


@pytest.mark.parametrize("seed", [2147483677, 17, 3_000_000_019])
def test_service_keeps_stated_bound_at_clustered_sites(seed):
    """The same at sites 6 bp apart, a variant per 12 bp, a quarter of them
    1-15 bp insertions and a quarter 1-4 bp deletions: the bound holds
    wherever its condition does, including reads past several alleles
    close together, and every answer is a valid alignment."""
    config = json.loads(POPULATION.read_text())
    config.update(reference_length=100_000, variant_every_bp=12,
                  variant_site_pitch=6,
                  variant_mix={"snp": 2, "ins": 1, "del": 1},
                  deletion_span={"1": 46, "2": 15, "3": 7, "4": 8})
    reads, res, bases, succ, backbone, variants = _service_answers(
        config, seed, 64)
    load = _allele_load(variants, config["reference_length"])
    bounded = _check_answers(reads, res, bases, succ, backbone, load)
    assert len(bounded) >= 32
    assert sum(n >= 16 for n in bounded) >= 8
