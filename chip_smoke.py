#!/usr/bin/env python3
"""First-light check of the read-mapping service on a TPU.

Drives the service's main path through the objects a user calls —
`build_epoched_index` / `build_epoched_graph_index`, `EngineConfig`,
`ServeEngine`, `Session` — at a realistic index size, checks what comes
out, and prints one JSON object as its last line:

    python3 chip_smoke.py              # one chip: linear + graph service
    python3 chip_smoke.py --chips 4    # four chips: sharded linear service

One chip:

* **linear** — a seeded random reference of hg38 chr1 length, indexed
  with the engine's seeding defaults (w=10, k=15), serves a queue of
  Illumina 150 bp reads plus 1 kbp reads (two bucket rungs).  The Pallas
  backend must run natively (no interpret mode); its PAF must be
  byte-identical to the ``lax`` backend's on the same chip; a sample of
  reads must sit within the paper's greedy-window slack (+3) of the
  exact DP oracle (`repro.align.refdp`); positions must be correct.
* **graph** — the same checks with GAF, ``graph_pallas`` against
  ``graph_lax``, over a variation graph whose backbone is cut to
  ``--graph-len`` (the graph index holds each node once, about 22 B
  per backbone base on the device) with one variant per 200 bp.

Four chips (``--chips 4``, this phase only): the linear service with
``num_shards=4`` on a 4-device mesh (``spmd`` asserted), with and
without the mesh-split align stage, each PAF byte-identical to
``num_shards=1`` on the same reads.

Exits non-zero and prints no result when JAX finds no TPU, when fewer
chips than asked are present, or when any check fails.  PAF/GAF files
land in ``chiprun_out/chip_smoke/``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

CHR1_LEN = 248_956_422  # hg38 chromosome 1
GRAPH_LEN = 10_000_000
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"
SLACK = 3  # paper §4.10.2: windowed GenASM is within +3 of the DP optimum
POS_TOL = 16  # position-correct tolerance (launch/serve_genomics.py)


class CheckFailed(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def require_tpu(chips: int):
    """The JAX devices, or CheckFailed when they are not ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX runs on {devs[0].platform}")
    check(len(devs) >= chips, f"asked for {chips} chips, found {len(devs)}")
    return devs


def assert_native(backend: str) -> None:
    """The backend is a Pallas one and its kernels compile natively."""
    from repro import align
    from repro.kernels import ops

    check(align.get_backend(backend).uses_pallas,
          f"backend {backend} runs no Pallas kernel")
    check(not align.needs_interpret() and not ops._interpret(),
          "Pallas kernels would run in interpret mode")


def nbytes(tree) -> int:
    import jax

    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def serve(index, cfg, reads, *, passes: int = 1):
    """Map ``reads`` through a fresh engine ``passes`` times.

    Returns ``(engine, [(gid, ServeResult)], [seconds per pass])``; the
    engine is closed.  The first pass includes every compilation.
    """
    from repro.serve import ServeEngine, Session

    times = []
    with ServeEngine(index, cfg) as engine:
        for _ in range(passes):
            sess = Session(engine)
            t0 = time.perf_counter()
            for gid, read in enumerate(reads):
                sess.submit(read, meta=gid)
            out = sess.drain()
            times.append(time.perf_counter() - t0)
    return engine, out, times


def write_rows(name: str, rows, writer) -> bytes:
    from repro.launch.serve_genomics import strip_gids

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = OUT_DIR / name
    writer(path, strip_gids(rows))
    return path.read_bytes()


def position_check(tag: str, rows, true_pos, n_reads: int) -> None:
    mapped = len(rows)
    correct = sum(abs(r["tstart"] - int(true_pos[r["gid"]])) <= POS_TOL
                  for r in rows)
    log(f"{tag}: mapped {mapped}/{n_reads} reads; position-correct "
        f"{correct}/{mapped}")
    check(mapped >= 0.95 * n_reads, f"{tag}: only {mapped}/{n_reads} mapped")
    check(correct >= 0.95 * mapped,
          f"{tag}: only {correct}/{mapped} position-correct")


def make_reads(ref, *, n_short: int, n_long: int, seed: int):
    """Illumina 150 bp reads then 1 kbp reads (same error profile)."""
    import numpy as np
    from repro.genomics import simulate

    short = simulate.simulate_reads(ref, n_reads=n_short, read_len=150,
                                    profile=simulate.ILLUMINA, seed=seed)
    long = simulate.simulate_reads(ref, n_reads=n_long, read_len=1000,
                                   profile=simulate.ILLUMINA, seed=seed + 1)
    return (short.reads + long.reads,
            np.concatenate([short.true_pos, long.true_pos]))


def engine_config(**kw):
    from repro.genomics import simulate
    from repro.serve import EngineConfig

    prof = simulate.ILLUMINA
    base = dict(
        buckets=(160, 320, 640, 1280), max_batch=32, max_delay_s=0.5,
        # the serving launcher's edit budget for 150 bp Illumina reads
        filter_k=max(8, int(150 * prof.error_rate * 1.5)),
        cache_capacity=0)
    base.update(kw)
    return EngineConfig(**base)


def linear_oracle_check(ref, results, reads, w: int) -> None:
    """Sampled reads against the exact DP oracle (`align.refdp`)."""
    import numpy as np
    from repro.align import refdp
    from repro.core import oracle

    short = [(g, r) for g, r in results if r.position >= 0
             and r.read_len <= 160]
    long = [(g, r) for g, r in results if r.position >= 0
            and r.read_len > 160]
    sample = short[:: max(1, len(short) // 24)][:24] + long[:2]
    gaps = []
    for gid, r in sample:
        read = np.asarray(reads[gid], np.int8)
        text = ref[r.position: r.position + r.bucket_cap + 2 * w]
        want = refdp.align_one(read, text, 1)[0]
        err = oracle.check_cigar(r.ops, r.n_ops, read, text, r.distance)
        check(err is None, f"read {gid}: CIGAR inconsistent: {err}")
        check(r.distance >= want,
              f"read {gid}: distance {r.distance} below the optimum {want}")
        if r.read_len <= 160:
            check(r.distance <= want + SLACK,
                  f"read {gid}: distance {r.distance} > oracle {want} + "
                  f"{SLACK}")
        gaps.append(r.distance - want)
    log(f"linear oracle: {len(sample)} reads checked against refdp; "
        f"distance - optimum: max {max(gaps)}, exact {gaps.count(0)}")


def graph_oracle_check(bases, succ, results, reads, w: int) -> None:
    """Sampled reads against the anchored sequence-to-graph DP oracle."""
    import numpy as np
    from repro.core import oracle
    from repro.core.segram.graph import HOP_LIMIT

    done = [(g, r) for g, r in results if r.position >= 0
            and r.read_len <= 160]
    sample = done[:: max(1, len(done) // 12)][:12]
    gaps = []
    for gid, r in sample:
        read = np.asarray(reads[gid], np.int8)
        path = [int(x) for x in r.path[: r.n_ops] if x >= 0]
        for a, b in zip(path, path[1:]):
            hop = b - a - 1
            check(0 <= hop < HOP_LIMIT and (int(succ[a]) >> hop) & 1,
                  f"read {gid}: path steps {a}->{b} off the graph")
        spelled = bases[path]
        err = oracle.check_cigar(r.ops, r.n_ops, read, spelled, r.distance)
        check(err is None, f"read {gid}: CIGAR inconsistent: {err}")
        o = path[0]
        t = min(r.bucket_cap + 2 * w, len(bases) - o)
        preds = [[] for _ in range(t)]
        for i in range(t):
            for h in range(HOP_LIMIT):
                j = i + h + 1
                if j < t and (int(succ[o + i]) >> h) & 1:
                    preds[j].append(i)
        want = oracle.graph_edit_distance_anchored(read, bases[o: o + t],
                                                   preds, start=0)
        check(want <= r.distance <= want + SLACK,
              f"read {gid}: distance {r.distance} vs oracle {want}")
        gaps.append(r.distance - want)
    log(f"graph oracle: {len(sample)} reads checked against the graph DP; "
        f"distance - optimum: max {max(gaps)}, exact {gaps.count(0)}")


def run_linear(args) -> None:
    from repro.core.minimizer_index import build_epoched_index
    from repro.genomics import io, simulate
    from repro.launch.serve_genomics import paf_row

    ref = simulate.random_reference(args.ref_len, seed=args.seed)
    t0 = time.perf_counter()
    epi = build_epoched_index(ref)  # the engine's seeding defaults
    log(f"linear: reference {args.ref_len} bp, index built in "
        f"{time.perf_counter() - t0:.1f} s, {nbytes(epi.index)} bytes on "
        f"the device ({len(epi.index.hashes)} minimizers)")
    reads, true_pos = make_reads(ref, n_short=args.reads,
                                 n_long=args.long_reads, seed=args.seed + 2)

    outs = {}
    for backend, passes in (("auto", 2), ("lax", 1)):
        engine, res, times = serve(epi, engine_config(align_backend=backend),
                                   reads, passes=passes)
        if backend == "auto":
            assert_native(engine.align_backend)
        caps = sorted({r.bucket_cap for _, r in res})
        log(f"linear {engine.align_backend}: {len(reads)} reads over "
            f"bucket caps {caps}; first pass (compile + run) "
            f"{times[0]:.1f} s" + (f", warm pass {times[1]:.1f} s"
                                   if passes > 1 else ""))
        rows = [paf_row(g, r, len(ref)) for g, r in res if r.position >= 0]
        outs[engine.align_backend] = (write_rows(
            f"linear_{engine.align_backend}.paf", rows, io.write_paf),
            res, rows)
    (kern, (pk, res, rows)), (_, (pl_, _, _)) = outs.items()
    check(len({r.bucket_cap for _, r in res}) > 1, "only one bucket rung ran")
    check(pk == pl_, f"linear: {kern} PAF differs from lax PAF")
    log(f"linear: {kern} PAF byte-identical to lax ({len(pk)} bytes)")
    position_check("linear", rows, true_pos, len(reads))
    linear_oracle_check(ref, res, reads, w=64)


def run_graph(args) -> None:
    import numpy as np
    from repro.genomics import io, simulate
    from repro.graph.index import build_epoched_graph_index
    from repro.graph.windowed import unpack_graph_text
    from repro.launch.serve_genomics import gaf_row

    cfg0 = engine_config(workload="graph")
    ref = simulate.random_reference(args.graph_len, seed=args.seed + 5)
    n_var = args.graph_len // 200
    variants = simulate.simulate_variants(
        ref, n_snp=n_var // 2, n_ins=n_var // 4, n_del=n_var // 4,
        seed=args.seed + 6)
    log(f"graph: backbone cut to {args.graph_len} bp (the index holds "
        f"~22 B per backbone base on the device), {len(variants)} variants")
    t0 = time.perf_counter()
    egi = build_epoched_graph_index(
        ref, variants, window=max(cfg0.buckets) + 2 * cfg0.genasm.w)
    garr = egi.index.arrays
    log(f"graph: index built in {time.perf_counter() - t0:.1f} s, "
        f"{nbytes(garr)} bytes on the device ({egi.index.n_nodes} nodes, "
        f"{egi.index.n_tiles} screen tiles of {egi.index.tile_len})")
    reads, true_pos = make_reads(ref, n_short=args.reads // 2,
                                 n_long=args.long_reads // 2,
                                 seed=args.seed + 7)

    outs = {}
    for backend in ("auto", "graph_lax"):
        engine, res, times = serve(
            egi, engine_config(workload="graph", align_backend=backend),
            reads)
        if backend == "auto":
            assert_native(engine.align_backend)
        log(f"graph {engine.align_backend}: {len(reads)} reads; first pass "
            f"(compile + run) {times[0]:.1f} s")
        rows = [gaf_row(g, r) for g, r in res if r.position >= 0]
        outs[engine.align_backend] = (write_rows(
            f"graph_{engine.align_backend}.gaf", rows, io.write_gaf),
            res, rows)
    (kern, (gk, res, rows)), (_, (gl, _, _)) = outs.items()
    check(gk == gl, f"graph: {kern} GAF differs from graph_lax GAF")
    log(f"graph: {kern} GAF byte-identical to graph_lax ({len(gk)} bytes)")
    position_check("graph", rows, true_pos, len(reads))
    bases, succ = unpack_graph_text(np.asarray(garr.gtext))
    graph_oracle_check(np.asarray(bases), np.asarray(succ), res, reads, w=64)


def run_sharded(args) -> None:
    from repro.core.minimizer_index import build_epoched_index
    from repro.genomics import io, simulate
    from repro.launch.serve_genomics import paf_row

    ref = simulate.random_reference(args.ref_len, seed=args.seed)
    t0 = time.perf_counter()
    epi = build_epoched_index(ref)
    log(f"sharded: reference {args.ref_len} bp, index built in "
        f"{time.perf_counter() - t0:.1f} s")
    reads, _ = make_reads(ref, n_short=args.reads, n_long=args.long_reads,
                          seed=args.seed + 2)
    pafs = {}
    for tag, kw in (("1 shard", {}),
                    ("4 shards", dict(num_shards=4)),
                    ("4 shards, align_sharded",
                     dict(num_shards=4, align_sharded=True))):
        engine, res, times = serve(epi, engine_config(**kw), reads)
        assert_native(engine.align_backend)
        if kw:
            spmd = [ex.spmd for ex in engine.executors]
            check(spmd and all(spmd), f"{tag}: executors not on a mesh")
        rows = [paf_row(g, r, len(ref)) for g, r in res if r.position >= 0]
        pafs[tag] = write_rows(
            f"sharded_{tag.replace(' ', '_').replace(',', '')}.paf", rows,
            io.write_paf)
        log(f"{tag}: {len(rows)}/{len(reads)} mapped, first pass (compile "
            f"+ run) {times[0]:.1f} s" + (", spmd" if kw else ""))
    for tag, paf in pafs.items():
        check(paf == pafs["1 shard"], f"{tag}: PAF differs from 1 shard")
    log(f"sharded: PAF byte-identical at 1 and 4 shards, with and without "
        f"align_sharded ({len(pafs['1 shard'])} bytes)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded four-chip phase")
    ap.add_argument("--ref-len", type=int, default=CHR1_LEN,
                    help="linear reference length (default: hg38 chr1; "
                         "one chip's share of a human genome is ~775 Mbp)")
    ap.add_argument("--graph-len", type=int, default=GRAPH_LEN,
                    help="graph backbone length")
    ap.add_argument("--reads", type=int, default=384,
                    help="150 bp reads in the linear queue (half for graph)")
    ap.add_argument("--long-reads", type=int, default=32,
                    help="1 kbp reads in the linear queue (half for graph)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        devs = require_tpu(args.chips)
        log(f"device: {devs[0].device_kind} x{len(devs)}")
        if args.chips == 4:
            run_sharded(args)
        else:
            run_linear(args)
            run_graph(args)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
