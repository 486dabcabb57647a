"""Data and reads from the seed: the cells that exist draw exactly what they
drew, indel lengths follow their tables, and haplotype reads walk the graph."""
import hashlib
import json

import numpy as np
import pytest

from bench.harness import cell, traffic
from bench.harness import reference as R

from .conftest import DATA, ROOT, TINY

CONFIGS = {"linear-chr1": ROOT / "bench/configs/linear-chr1.json",
           "graph-10m": DATA / "graph-10m.json",
           "graph-1kg": DATA / "graph-1kg.json"}
# sha256 prefixes of (data, plan) at the tiny sizes, 0.5 s, max_batch 32,
# recorded from the harness before indel tables and haplotypes existed
GOLDEN = {
    7: {"linear-chr1/sr-batch": ("ec784cb50f6b08a2", "b5c336c20dedd519"),
        "linear-chr1/sr-online": ("ec784cb50f6b08a2", "8099bbb0cf3c9fb6"),
        "graph-10m/sr-batch": ("b7d97bde68c2184e", "09fc7ea8039e1d96")},
    2 ** 31 + 11: {
        "linear-chr1/sr-batch": ("1ac0cebcd5a979d2", "e124d6bf47dbf67f"),
        "linear-chr1/sr-online": ("1ac0cebcd5a979d2", "27de637200e626df"),
        "graph-10m/sr-batch": ("c9d65d2d9eb9e9dd", "0fc4f46325cd8095")},
    4_000_000_019: {
        "linear-chr1/sr-batch": ("24f1ab9ec895dd84", "df396a1ddd2e2230"),
        "linear-chr1/sr-online": ("24f1ab9ec895dd84", "4cc7a913c2add533"),
        "graph-10m/sr-batch": ("963de8c73720bcee", "dc66e180fb2e7612")},
}


def tiny_config(name: str) -> dict:
    config = json.loads(CONFIGS[name].read_text())
    config["reference_length"] = TINY[config["system"]]
    return config


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def draw(name: str, traffic_name: str, seed: int, error_rate=None):
    config = tiny_config(name)
    mix = json.loads((ROOT / f"bench/traffic/{traffic_name}.json")
                     .read_text())
    if error_rate is not None:
        mix["error_profile"] = dict(mix["error_profile"], rate=error_rate)
    rngs = cell.streams(seed)
    data = cell.make_data(config, rngs["data"])
    plan = traffic.plan(mix, cell.read_source(config, data,
                                              rngs["haplotype"]),
                        seconds=0.5, max_batch=32, rng_warm=rngs["warmup"],
                        rng_window=rngs["window"],
                        rng_arrival=rngs["arrival"])
    return config, data, plan


@pytest.mark.parametrize("seed", sorted(GOLDEN))
def test_existing_cells_draw_what_they_drew(seed):
    for key, want in GOLDEN[seed].items():
        name, traffic_name = key.split("/")
        _, data, plan = draw(name, traffic_name, seed)
        got_data = digest([data.reference] + ([] if data.variants is None
                                              else list(data.variants)))
        due = [] if plan.due is None else [plan.due]
        got_plan = digest(list(plan.warmup) + list(plan.window) + due
                          + [np.array(plan.outstanding)])
        assert (got_data, got_plan) == want, key


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 7, 4_000_000_007])
def test_indel_lengths_follow_their_tables_and_never_overlap(seed):
    config = tiny_config("graph-1kg")
    data = cell.make_data(config, np.random.default_rng(seed))
    v = data.variants
    for kind, key, got in ((1, "insertion_length", v.alt_len),
                           (2, "deletion_span", v.span)):
        table = R.length_table(config[key])
        mine = got[v.kind == kind]
        share = len(mine) / sum(table.values())
        for length, w in table.items():
            assert abs(np.sum(mine == length) - w * share) < 1, (key, length)
        assert set(np.unique(mine)) <= set(table)
    reach = v.pos + v.span + 1  # where a deletion lands; else the next base
    assert np.all(v.pos[1:] > reach[:-1])
    kinds = np.bincount(v.kind, minlength=3)
    assert kinds[0] > 40 * kinds[1] and abs(int(kinds[1]) - kinds[2]) <= 1
    R.build_graph(data.reference, v)  # every edge fits the hop bits


def test_integer_lengths_and_tables_are_checked():
    ref = R.random_reference(2000, np.random.default_rng(0))
    kw = dict(every_bp=20, mix={"snp": 1, "del": 1}, ins_len=2,
              rng=np.random.default_rng(1))
    with pytest.raises(ValueError, match="site pitch"):
        R.random_variants(ref, del_span={"1": 1, "5": 1}, site_pitch=6, **kw)
    with pytest.raises(ValueError, match="1..15"):
        R.random_variants(ref, del_span=16, site_pitch=30, **kw)
    v = R.random_variants(ref, del_span={"1": 1, "5": 1}, site_pitch=7, **kw)
    assert set(np.unique(v.span[v.kind == 2])) == {1, 5}


def haplotype_of(name: str, seed: int):
    config = tiny_config(name)
    rngs = cell.streams(seed)
    data = cell.make_data(config, rngs["data"])
    hap = R.haplotype(data.reference, data.variants,
                      config["sample_alt_share"], rngs["haplotype"])
    return config, data, hap


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 3, 4_000_000_033])
def test_haplotype_reads_walk_the_graph_from_their_true_pos(seed):
    config, data, hap = haplotype_of("graph-1kg", seed)
    v = data.variants
    g = R.build_graph(data.reference, v)
    c = R.coords(len(data.reference), v)
    walk = hap.nodes(c, np.arange(len(hap.seq)))
    assert R.path_error(g, np.zeros(len(walk), np.int8), walk, hap.seq, 0,
                        0) is None  # the whole haplotype is one graph path
    alt_taken = np.isin(c.site + 1, walk) & (v.kind != 2)
    step = np.searchsorted(walk, c.site[v.kind == 2])
    del_taken = walk[np.minimum(step + 1, len(walk) - 1)] == c.node(
        v.pos + v.span + 1)[v.kind == 2]
    share = (alt_taken.sum() + del_taken.sum()) / len(v.pos)
    assert abs(share - config["sample_alt_share"]) < 0.01

    lengths = np.repeat([150, 250], 200)
    exact = {"rate": 0.0, "sub": 0.8, "ins": 0.1, "del": 0.1}
    starts = traffic.simulate_reads(hap.seq, lengths, exact,
                                    np.random.default_rng(seed)).true_pos
    reads = traffic.simulate_reads(hap.seq, lengths, exact,
                                   np.random.default_rng(seed),
                                   hap.first_backbone)
    crossing = 0
    for i, q in enumerate(starts):
        path = walk[q:q + lengths[i]]
        assert R.path_error(g, np.zeros(len(path), np.int8), path,
                            reads.read(i), 0, int(reads.true_pos[i])) is None
        crossing += bool(np.any(g.backbone[path] < 0))
    assert crossing > 20  # reads that take an alt branch


def test_a_cell_with_an_alt_share_reads_from_the_haplotype():
    config, data, plan = draw("graph-1kg", "sr-batch", 11, error_rate=0.0)
    _, _, hap = haplotype_of("graph-1kg", 11)
    seq, backbone = hap.seq.tobytes(), data.reference.tobytes()
    off_backbone = 0
    for i in range(0, len(plan.window), 13):
        read = plan.window.read(i).tobytes()
        q = seq.find(read)
        assert q >= 0 and hap.first_backbone(q) == plan.window.true_pos[i]
        off_backbone += backbone.find(read) < 0
    assert off_backbone > 10
    config.pop("sample_alt_share")
    rngs = cell.streams(11)
    source = cell.read_source(config, cell.make_data(config, rngs["data"]),
                              rngs["haplotype"])
    assert source.to_backbone is None and source.seq.shape == (TINY["graph"],)
