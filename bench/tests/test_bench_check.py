"""The graph check builds only the nodes around each answer, and reads what
a check over the whole graph reads."""
import numpy as np
import pytest

from bench.harness import cell, check
from bench.harness import reference as R
from bench.harness.traffic import Reads

from .test_bench_data import tiny_config

PROFILE = {"rate": 0.05, "sub": 0.8, "ins": 0.1, "del": 0.1}


def whole_graph_check(sample, ans, reads, data):
    """The check as it stood: the whole graph built, then each answer."""
    graph = R.build_graph(data.reference, data.variants)
    bad, rows, starts, lens = 0, [], [], []
    for i in sample:
        position, distance, ops, path = ans[i]
        read = reads.read(i)
        span = 2 * (len(read) + max(distance, 0) + 8)
        if R.path_error(graph, ops, path, read, distance, position):
            bad += 1
            continue
        consumed = path[path >= 0]
        rows.append(i)
        starts.append(int(consumed[0]) if len(consumed) else 0)
        lens.append(span)
    if not rows:
        return bad, 0
    b, s = R.windows(graph.bases, graph.succ, np.array(starts),
                     np.array(lens))
    opt = R.anchored_distance([reads.read(i) for i in rows], b, s)
    dist = np.array([ans[i][1] for i in rows])
    return bad + int(np.sum(dist < opt)), int(np.max(dist - opt))


def answers(config, seed):
    """Spelled answers from a haplotype, some of them broken: a distance
    off by one either way, a path that leaves the edges, jumps far or
    leaves the graph, a wrong position, and reads at both ends."""
    rngs = cell.streams(seed)
    data = cell.make_data(config, rngs["data"])
    hap = R.haplotype(data.reference, data.variants,
                      config.get("sample_alt_share", 0.0),
                      rngs["haplotype"])
    c = R.coords(len(data.reference), data.variants)
    rng = np.random.default_rng(seed)
    n = len(hap.seq)
    lengths = rng.choice([150, 250], size=48)
    starts = rng.integers(0, n - 250, size=48)
    starts[:2] = (0, n - lengths[1])
    got = [R.spelled_read(hap, c, int(q), int(k), PROFILE, rng)
           for q, k in zip(starts, lengths)]
    reads = [r for r, _ in got]
    ans = [a for _, a in got]
    broken = []
    for j, what in enumerate(("up", "down", "edge", "far", "outside",
                              "position", "up", "edge")):
        position, distance, ops, path = ans[2 + j]
        path = path.copy()
        on = np.nonzero(path >= 0)[0]
        if what == "up":
            distance += 1
        elif what == "down":
            distance -= 1
        elif what == "edge":
            path[on[len(on) // 2]:] += 40
        elif what == "far":
            path[on[-3:]] += 10 ** 6
        elif what == "outside":
            path[on[-1]] = c.total + 5
        else:
            position += 1
        broken.append((position, distance, ops, path))
    ans[2:2 + len(broken)] = broken
    lens = np.array([len(r) for r in reads])
    return data, Reads(np.concatenate(reads),
                       np.concatenate([[0], np.cumsum(lens)]),
                       np.zeros(len(reads), np.int64)), ans


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5, 3_000_000_017,
                                  4_294_967_311])
@pytest.mark.parametrize("name", ["graph-10m", "graph-1kg"])
def test_local_graphs_check_as_the_whole_graph_does(name, seed):
    config = tiny_config(name)
    data, reads, ans = answers(config, seed)
    sample = list(range(len(ans)))
    want = whole_graph_check(sample, dict(enumerate(ans)), reads, data)
    got = check._alignments(sample, dict(enumerate(ans)), reads, data)
    assert got == want
    assert want[0] >= 6 and want[1] >= 1  # the broken answers show
    whole = R.build_graph(data.reference, data.variants)
    c = R.coords(len(data.reference), data.variants)
    assert c.total == len(whole.bases)
    for i, (position, distance, ops, path) in enumerate(ans):
        span = 2 * (len(reads.read(i)) + 8)
        g, _ = R.answer_graph(data.reference, data.variants, c, path, span)
        assert len(g.bases) < 4 * span
        assert (R.path_error(g, ops, path, reads.read(i), distance, position)
                == R.path_error(whole, ops, path, reads.read(i), distance,
                                position))


@pytest.mark.parametrize("name", ["graph-10m", "graph-1kg"])
def test_local_graph_nodes_are_the_whole_graphs(name):
    config = tiny_config(name)
    data = cell.make_data(config, np.random.default_rng(8))
    whole = R.build_graph(data.reference, data.variants)
    c = R.coords(len(data.reference), data.variants)
    assert np.array_equal(c.node(np.arange(len(data.reference))),
                          np.nonzero(whole.backbone >= 0)[0])
    for first in (0, 1, 977, 50_000, c.total - 300, c.total - 1):
        last = min(first + 299, c.total - 1)
        g = R.local_graph(data.reference, data.variants, c, first, last)
        lo, hi = first - g.first, last - g.first + 1
        assert lo >= 0 and hi <= len(g.bases)
        ids = slice(first, last + 1)
        assert np.array_equal(g.bases[lo:hi], whole.bases[ids])
        assert np.array_equal(g.backbone[lo:hi], whole.backbone[ids])
        inside = np.minimum(last - np.arange(first, last + 1), 32)
        mask = np.where(inside >= 32, np.uint32(0xFFFFFFFF),
                        (np.uint32(1) << inside.astype(np.uint32)) - 1)
        assert np.array_equal(g.succ[lo:hi] & mask, whole.succ[ids] & mask)
