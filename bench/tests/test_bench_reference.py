"""The plain reference agrees with the textbook oracles on small cases."""
import numpy as np
import pytest

from bench.harness import reference as R


def oracle_anchored(pattern, bases, succ):
    """Textbook loop DP of the anchored read-to-graph distance."""
    n, m = len(bases), len(pattern)
    preds = [[] for _ in range(n)]
    for i in range(n):
        for h in range(R.HOP_LIMIT):
            if (int(succ[i]) >> h) & 1 and i + h + 1 < n:
                preds[i + h + 1].append(i)
    inf = 10 ** 9
    a = np.full((m + 1, n), inf, np.int64)
    for j in range(m + 1):
        for i in range(n):
            cost = 0 if j > 0 and pattern[j - 1] == bases[i] else 1
            best = inf
            if i == 0:
                best = j + 1
                if j > 0:
                    best = min(best, j - 1 + cost)
            if j > 0:
                best = min(best, a[j - 1][i] + 1)
            for p in preds[i]:
                if j > 0:
                    best = min(best, a[j - 1][p] + cost)
                best = min(best, a[j][p] + 1)
            a[j][i] = best
    return int(min(a[m].min(), m))


LONG = {"1": 2, "7": 1, "15": 1}  # indel lengths up to the hop bits' 15


def small_graph(seed, long=False):
    rng = np.random.default_rng(seed)
    ref = R.random_reference(600 if long else 300, rng)
    kw = (dict(ins_len=LONG, del_span=LONG, site_pitch=17) if long
          else dict(ins_len=2, del_span=2, site_pitch=6))
    v = R.random_variants(ref, every_bp=12, mix={"snp": 2, "ins": 1, "del": 1},
                          rng=rng, **kw)
    return ref, v, R.build_graph(ref, v)


@pytest.mark.parametrize("seed", range(4))
def test_graph_matches_the_programs_linearization(seed):
    linearizations_agree(seed, long=False)


@pytest.mark.parametrize("seed", range(4))
def test_graph_with_long_indels_matches_the_programs_linearization(seed):
    linearizations_agree(seed, long=True)


def linearizations_agree(seed, long):
    from repro.core.segram.graph import Variant, build_graph

    ref, v, g = small_graph(seed, long)
    want = build_graph(ref, [
        Variant(int(p), R.KINDS[k], tuple(int(b) for b in a[:n]), int(s))
        for p, k, a, n, s in zip(v.pos, v.kind, v.alt, v.alt_len, v.span)])
    assert np.array_equal(g.bases, want.bases)
    assert np.array_equal(g.succ, want.succ_bits)
    assert np.array_equal(g.backbone, want.backbone)


@pytest.mark.parametrize("seed", range(3))
def test_anchored_distance_matches_the_loop_dp(seed):
    distances_agree(seed, long=False)


@pytest.mark.parametrize("seed", range(3))
def test_anchored_distance_with_long_indels_matches_the_loop_dp(seed):
    distances_agree(seed, long=True)


def distances_agree(seed, long):
    rng = np.random.default_rng(seed)
    ref, v, g = small_graph(seed, long)
    starts = rng.integers(0, len(g.bases) - 60, size=6)
    lens = np.full(6, 60)
    b, s = R.windows(g.bases, g.succ, starts, lens)
    reads = [g.bases[st + 2: st + 2 + k].copy() for st, k in
             zip(starts, rng.integers(20, 40, size=6))]
    for r in reads:
        r[::7] = (r[::7] + 1) % 4
    got = R.anchored_distance(reads, b, s)
    want = [oracle_anchored(r, b[i], s[i]) for i, r in enumerate(reads)]
    assert got.tolist() == want
    lin_b, lin_s = R.windows(ref, None, starts[:3], lens[:3])
    got = R.anchored_distance(reads[:3], lin_b, lin_s)
    assert got.tolist() == [oracle_anchored(r, lin_b[i], lin_s[i])
                            for i, r in enumerate(reads[:3])]


def test_cigar_and_path_checks_catch_faults():
    ref, v, g = small_graph(1)
    read = g.bases[10:30].copy()
    ops = np.zeros(20, np.int8)
    assert R.cigar_error(ops, read, g.bases[10:40], 0) is None
    assert R.cigar_error(ops, read, g.bases[10:40], 1) is not None
    read2 = read.copy()
    read2[5] = (read2[5] + 1) % 4
    assert R.cigar_error(ops, read2, g.bases[10:40], 0) is not None
    bb = np.nonzero(g.backbone >= 0)[0]
    path = bb[100:120]
    read3 = g.bases[path]
    ops3 = np.zeros(len(path), np.int8)
    pos = int(g.backbone[path[0]])
    assert R.path_error(g, ops3, path, read3, 0, pos) is None
    assert R.path_error(g, ops3, path, read3, 0, pos + 1) is not None
    hole = path.copy()
    hole[5:] += 20  # jumps along no edge
    assert R.path_error(g, ops3, hole, g.bases[hole], 0, pos) is not None
