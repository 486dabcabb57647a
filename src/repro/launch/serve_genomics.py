"""Read-mapping service driver (the paper's workload, end-to-end).

Both serving modes sit on the same ``repro.serve`` micro-batching engine
(length-bucketed padding, per-bucket compiled executors, result cache —
DESIGN.md §8), so they produce identical output for the same read set:

* **offline** (default) — drain a fixed read set through the lease-based
  work queue (straggler/failure reassignment, DESIGN.md §6); each claimed
  quantum's reads are submitted to the engine.
* **``--online``** — synthetic open-loop Poisson arrivals through the
  engine's admission queue (`serve/session.py`), reporting reads/s and
  tail latency.

Both compose with the workload axis (DESIGN.md §10): ``--mode linear``
emits PAF against a linear reference, ``--mode graph`` builds a
variation-graph index and emits GAF (node path + CIGAR) through the
``graph_lax``/``graph_pallas`` backends — and with the sharding axis
(DESIGN.md §11): ``--num-shards N`` partitions the reference index
across N devices (`repro.shard` scatter/merge), byte-identical output.

The observability plane (DESIGN.md §12) attaches with two flags:
``--trace-out trace.json`` traces every flush and writes a
Perfetto/Chrome ``trace_event`` file plus the per-stage Amdahl
attribution table on exit; ``--http-port N`` serves ``/metrics``,
``/healthz``, ``/trace``, and ``/attrib`` from a daemon thread while
the run is live (port 0 = ephemeral).

On a pod this runs one process per host with reads sharded by
process_index.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import minimizer_index
from repro.core.genasm import GenASMConfig
from repro.dist.fault import WorkQueue
from repro.serve import EngineConfig, ServeEngine, Session, poisson_load
from repro.genomics import io, simulate
from repro.launch.compile_cache import enable_compile_cache


def paf_row(gid: int, res, ref_len: int) -> dict:
    """PAF row dict for one mapped read.

    Carries the global read id in ``"gid"`` (not a PAF column — strip via
    `strip_gids` before `io.write_paf`), so qnames can be arbitrary
    instead of being parsed back into ids.
    """
    L = res.read_len
    return {
        "gid": gid,
        "qname": f"read{gid}", "qlen": L, "qstart": 0,
        "qend": L, "strand": "+", "tname": "ref",
        "tlen": ref_len, "tstart": res.position,
        "tend": res.position + L, "nmatch": L - res.distance,
        "alnlen": L, "mapq": 60,
        "cigar": io.cigar_string(res.ops, res.n_ops),
    }


def gaf_row(gid: int, res) -> dict:
    """GAF row dict for one graph-mapped read (node path + CIGAR).

    ``"tstart"`` (backbone coordinate of the first aligned node) rides
    along for position accounting — neither writer emits it.
    """
    L = res.read_len
    pstr, plen = io.gaf_path(res.path if res.path is not None else ())
    return {
        "gid": gid,
        "qname": f"read{gid}", "qlen": L, "qstart": 0,
        "qend": L, "strand": "+", "path": pstr,
        "plen": plen, "pstart": 0, "pend": plen,
        "nmatch": L - res.distance, "alnlen": int(res.n_ops), "mapq": 60,
        "tstart": res.position,
        "cigar": io.cigar_string(res.ops, res.n_ops),
    }


def strip_gids(rows: list[dict]) -> list[dict]:
    return [{k: v for k, v in r.items() if k != "gid"} for r in rows]


def _run_offline(engine: ServeEngine, reads, shard_ids, *, batch: int,
                 lease_s: float, row_fn) -> list[dict]:
    """Work-queue path: claim a quantum of read ids, submit it, complete."""
    quanta = [shard_ids[i: i + batch] for i in range(0, len(shard_ids), batch)]
    q = WorkQueue(len(quanta), lease_s=lease_s)
    rows: dict[int, dict] = {}  # keyed by gid: stolen twins overwrite, not dup
    while True:
        b = q.claim()
        if b is None:
            if q.finished:
                break
            time.sleep(0.01)  # all leases live; back off and retry
            continue
        sess = Session(engine)
        for gid in quanta[b]:
            sess.submit(reads[gid], meta=int(gid))
        for gid, res in sess.drain():
            if res.position >= 0:
                rows[gid] = row_fn(gid, res)
        q.complete(b)
    return [rows[g] for g in sorted(rows)]


def _run_online(engine: ServeEngine, reads, shard_ids, *, rate_rps: float,
                seed: int, row_fn) -> tuple[list[dict], object]:
    """Poisson open-loop path through the engine's admission queue."""
    rep = poisson_load(engine, [reads[g] for g in shard_ids],
                       rate_rps=rate_rps, seed=seed,
                       metas=[int(g) for g in shard_ids])
    rows = [row_fn(gid, res) for gid, res in rep.results
            if res.position >= 0]
    return sorted(rows, key=lambda r: r["gid"]), rep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ref-len", type=int, default=20_000)
    ap.add_argument("--reads", type=int, default=64)
    ap.add_argument("--read-len", type=int, default=150)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--profile", default="illumina",
                    choices=list(simulate.PROFILES))
    ap.add_argument("--out", default=None, help="PAF/GAF output path")
    ap.add_argument("--lease-s", type=float, default=600.0,
                    help="work-queue lease; expired leases are stolen")
    ap.add_argument("--mode", default="linear", choices=("linear", "graph"),
                    help="linear reference → PAF, or variation graph → GAF "
                         "(DESIGN.md §10)")
    ap.add_argument("--variants", type=int, default=None,
                    help="--mode graph: simulated variant count "
                         "(default ref_len // 200)")
    ap.add_argument("--align-backend", default="auto",
                    help="repro.align backend: auto|ref|lax|pallas_dc|"
                         "pallas_dc_v2|graph_lax|graph_pallas (auto = Pallas "
                         "on TPU/GPU, lax on CPU, graph twins under --mode "
                         "graph; env REPRO_ALIGN_BACKEND overrides auto)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="deprecated alias for --align-backend pallas_dc")
    ap.add_argument("--num-shards", type=int, default=1,
                    help="shard the reference index over N devices "
                         "(repro.shard scatter/merge; works on CPU via "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N"
                         ", falling back to a vmapped single-device "
                         "execution with identical output when fewer "
                         "devices exist); PAF/GAF is byte-identical to "
                         "--num-shards 1")
    ap.add_argument("--align-sharded", action="store_true",
                    help="with --num-shards > 1: split the winning-window "
                         "align stage over the shard mesh too "
                         "(byte-identical output)")
    ap.add_argument("--pipelined", action="store_true",
                    help="with --num-shards > 1: double-buffer flushes — "
                         "overlap batch i's align with batch i+1's "
                         "scatter dispatch (byte-identical output)")
    ap.add_argument("--online", action="store_true",
                    help="open-loop Poisson arrivals instead of the "
                         "offline work-queue drain")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="--online arrival rate (reads/s)")
    ap.add_argument("--buckets", default="160,320,640,1280",
                    help="length-bucket ladder of pattern caps")
    ap.add_argument("--max-delay-ms", type=float, default=5.0,
                    help="micro-batch flush deadline")
    ap.add_argument("--trace-out", default=None,
                    help="trace every flush and write Perfetto/Chrome "
                         "trace_event JSON here (plus the per-stage "
                         "Amdahl table on exit)")
    ap.add_argument("--http-port", type=int, default=None,
                    help="serve /metrics /healthz /trace /attrib /roofline "
                         "on this port while running (0 = ephemeral)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    prof = simulate.PROFILES[args.profile]
    ref = simulate.random_reference(args.ref_len, seed=1)
    rs = simulate.simulate_reads(ref, n_reads=args.reads,
                                 read_len=args.read_len, profile=prof, seed=2)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    need = ((args.read_len + 63) // 64) * 64 + 64  # offline driver's old cap
    if max(buckets) < need:  # never trim reads the single-cap path held
        buckets += (need,)
    if args.use_kernel and args.align_backend != "auto":
        ap.error("--use-kernel is a deprecated alias for --align-backend "
                 "pallas_dc; don't combine it with an explicit "
                 "--align-backend")
    backend = "pallas_dc" if args.use_kernel else args.align_backend
    genasm = GenASMConfig()

    if args.mode == "graph":
        from repro.graph import index as graph_index

        n_var = args.variants if args.variants is not None \
            else max(args.ref_len // 200, 4)
        variants = simulate.simulate_variants(
            ref, n_snp=n_var // 2, n_ins=n_var // 4, n_del=n_var // 4, seed=3)
        print(f"indexing variation graph ({args.ref_len} bp backbone, "
              f"{len(variants)} variants)...")
        epi = graph_index.build_epoched_graph_index(
            ref, variants, w=8, k=12,
            window=max(buckets) + 2 * genasm.w)  # largest bucket's t_cap
        row_fn, writer = gaf_row, io.write_gaf
    else:
        print(f"indexing reference ({args.ref_len} bp)...")
        epi = minimizer_index.build_epoched_index(ref, w=8, k=12)
        row_fn = lambda gid, res: paf_row(gid, res, args.ref_len)  # noqa: E731
        writer = io.write_paf

    cfg = EngineConfig(
        buckets=buckets, max_batch=args.batch,
        max_delay_s=args.max_delay_ms / 1e3,
        genasm=genasm,
        align_backend=backend,
        workload=args.mode,
        filter_k=max(8, int(args.read_len * prof.error_rate * 1.5)),
        num_shards=args.num_shards,
        align_sharded=args.align_sharded,
        pipelined=args.pipelined,
        minimizer_w=8, minimizer_k=12)

    pi, pc = jax.process_index(), jax.process_count()
    shard_ids = np.arange(pi, args.reads, pc)  # this host's disjoint slice

    tracer = None
    roofline = None
    if args.trace_out or args.http_port is not None:
        from repro.obs import RooflineManager, Tracer

        tracer = Tracer()
        roofline = RooflineManager(tracer=tracer)

    obs_server = None
    with ServeEngine(epi, cfg, tracer=tracer, roofline=roofline) as engine:
        if roofline is not None:
            roofline.metrics = engine.metrics
        if args.http_port is not None:
            from repro.obs.http import ObsServer

            obs_server = ObsServer(metrics=engine.metrics, tracer=tracer,
                                   roofline=roofline, port=args.http_port)
            print(f"obs endpoints at {obs_server.url} "
                  f"(/metrics /healthz /trace /attrib /roofline)")
        print(f"align backend: {engine.align_backend}")
        t0 = time.time()
        if args.online:
            rows, rep = _run_online(engine, rs.reads, shard_ids,
                                    rate_rps=args.rate, seed=7, row_fn=row_fn)
            print(f"online: {rep.reads_per_s:.1f} reads/s, "
                  f"p50 {rep.p50_ms:.1f} ms, p99 {rep.p99_ms:.1f} ms")
        else:
            rows = _run_offline(engine, rs.reads, shard_ids,
                                batch=args.batch, lease_s=args.lease_s,
                                row_fn=row_fn)
        dt = time.time() - t0
        m = engine.metrics.snapshot()
        hit_rate = engine.cache.hit_rate
    if obs_server is not None:
        obs_server.close()
    if tracer is not None:
        from repro.obs import build_ledger, render_report

        print(render_report(build_ledger(tracer.log).report()))
        if roofline is not None:
            # measure=False: no cost_analysis compiles at shutdown
            for row in roofline.report(measure=False)["kernels"]:
                print(f"roofline {row['kernel']}: "
                      f"{row['achieved_ops_per_s'] / 1e9:.2f} Gop/s, "
                      f"intensity {row['intensity']:.2f} op/B, "
                      f"{row['pct_of_roof']:.2%} of roof")
        if args.trace_out:
            tracer.log.export_chrome(args.trace_out)
            print(f"wrote {args.trace_out}")

    mapped = len(rows)
    correct = sum(
        1 for r in rows if abs(r["tstart"] - rs.true_pos[r["gid"]]) <= 16)
    occ = m.get("batch_occupancy_mean", 0.0)
    useful = m.get("bases_useful", 0.0)
    waste = m.get("bases_padded_read", 0.0)
    print(f"mapped {mapped}/{len(shard_ids)} reads in {dt:.2f}s "
          f"({len(shard_ids) / dt if dt else 0.0:.1f} reads/s); "
          f"position-correct: {correct}/{mapped}")
    print(f"batch occupancy {occ:.2f}, padded-base waste "
          f"{waste / max(useful + waste, 1):.1%}, "
          f"cache hit rate {hit_rate:.1%}")
    if args.out:
        writer(args.out, strip_gids(rows))
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
