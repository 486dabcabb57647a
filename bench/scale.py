#!/usr/bin/env python3
"""Time and memory of the benchmark's own data and check path, without the
program: what a configuration costs every run before and after the window.

    python3 bench/scale.py --config bench/tests/data/graph-1kg.json \
        --reference-length 248956422 --seed 7
    python3 bench/scale.py --config bench/tests/data/graph-1kg.json \
        --reference-length 248956422 --seed 7 --whole-graph

Stages, each timed on the host clock, with the process's peak resident
memory after it: the deployment's data from the seed (``make_data``), the
read source (with ``sample_alt_share``, the sample's haplotype), the
reads (``--reads`` of the mix's lengths, drawn as a window's are), and
the check of ``--sample`` answers plus the longest read, as a run checks
the window's (``check._alignments``).  The answers are the ones the
reference spells itself (``reference.spelled_read``: each read with the
mix's edits and the CIGAR and graph path that made it), so the check
does all of its work: no answer is bad, and each distance is the edits
made, at or above the optimum.  ``--whole-graph`` instead
builds the whole linearized graph once, as the check did before it built
only the nodes around each answer.  One JSON line per stage.
"""
import argparse
import json
import resource
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def _peak_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", type=Path, required=True)
    ap.add_argument("--reference-length", type=int)
    ap.add_argument("--mix", type=Path,
                    default=ROOT / "bench/traffic/sr-batch.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--reads", type=int, default=4096)
    ap.add_argument("--sample", type=int, default=256)
    ap.add_argument("--whole-graph", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from bench.harness import cell, check, traffic
    from bench.harness import reference as R

    config = json.loads(args.config.read_text())
    if args.reference_length:
        config["reference_length"] = args.reference_length
    mix = json.loads(args.mix.read_text())
    rngs = cell.streams(args.seed)
    t_start = time.monotonic()

    def stage(name: str, t0: float, **extra) -> None:
        print(json.dumps({"stage": name, "seconds": time.monotonic() - t0,
                          "peak_rss_mb": _peak_mb(), **extra}), flush=True)

    t0 = time.monotonic()
    data = cell.make_data(config, rngs["data"])
    v = data.variants
    stage("make_data", t0, bases=len(data.reference),
          variants=0 if v is None else len(v.pos))
    if args.whole_graph:
        t0 = time.monotonic()
        g = R.build_graph(data.reference, v)
        stage("whole_graph", t0, nodes=len(g.bases))
        return 0

    t0 = time.monotonic()
    hap = None
    if "sample_alt_share" in config:  # as cell.read_source draws it
        hap = R.haplotype(data.reference, v, config["sample_alt_share"],
                          rngs["haplotype"])
        source = traffic.Source(hap.seq, hap.first_backbone)
    else:
        source = cell.read_source(config, data, rngs["haplotype"])
    stage("read_source", t0, bases=len(source.seq))

    t0 = time.monotonic()
    lengths = traffic.class_lengths(mix, args.reads, rngs["window"])
    reads = traffic.simulate_reads(source.seq, lengths, mix["error_profile"],
                                   rngs["window"], source.to_backbone)
    stage("reads", t0, reads=len(reads))

    if v is None:
        return 0
    if hap is None:  # reads from the backbone: the haplotype that takes none
        hap = R.haplotype(data.reference, v, 0.0, rngs["extra"])
    nodes = R.coords(len(data.reference), v)
    rng = rngs["sample"]
    starts = rng.integers(0, len(hap.seq) - lengths.max(), size=args.sample)
    pick = np.concatenate([starts[:, None], lengths[:args.sample, None]], 1)
    pick = np.vstack([pick, [[starts[0], lengths.max()]]])
    spelled = [R.spelled_read(hap, nodes, int(q), int(n),
                              mix["error_profile"], rng) for q, n in pick]
    lens = np.array([len(r) for r, _ in spelled])
    answered = traffic.Reads(
        np.concatenate([r for r, _ in spelled]),
        np.concatenate([[0], np.cumsum(lens)]),
        np.array([a[0] for _, a in spelled], np.int64))
    answers = {i: a for i, (_, a) in enumerate(spelled)}

    t0 = time.monotonic()
    bad, excess = check._alignments(list(answers), answers, answered, data)
    stage("check", t0, answers=len(answers), bad_alignments=bad,
          excess_max=excess)
    stage("total", t_start)
    return 0


if __name__ == "__main__":
    sys.exit(main())
