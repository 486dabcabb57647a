"""Reference-sharding throughput: reads/s vs 1/2/4 host-platform shards.

Measures bucket-executor mapping throughput (engine admission excluded)
at two operating points:

* **filter-dominated** (the default/top-level numbers) — a large
  per-read candidate budget, the high-sensitivity regime the paper's
  GenASM-DC pre-alignment filter exists for (§4.10.3: many candidate
  locations per read).  The scatter stage strong-scales with shards.
* **align-dominated** (``align_point``) — long reads at a long bucket
  cap with a small candidate budget, where the winning-window align
  stage is most of the batch and the old single-device align was the
  Amdahl floor.

Each sharded row reports four modes so the win decomposes:

* ``reads_per_s_host_merge`` — the pre-device-merge path (per-shard
  winners synced to the host, lexicographic merge in numpy, align
  re-dispatched): the historical Amdahl floor.
* ``reads_per_s`` — packed-key argmin merge on device (winners never
  visit the host between scatter and align).
* ``reads_per_s_align_sharded`` — device merge plus the align stage
  mesh-split over the same shards.
* ``reads_per_s_pipelined`` — device merge + sharded align dispatched
  through ``start``/``finish`` double-buffering, batch i's align
  overlapping batch i+1's scatter.

Runs in one process.  The sharded rows use a shard mesh when
``jax.device_count() >= 4``; on the CPU backend give the process four
host devices from outside (XLA fixes the device count at first backend
use), otherwise the shards run as a stacked vmap on one device:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python benchmarks/shard_scaling.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

try:
    from .common import row
except ImportError:  # script-style: python benchmarks/shard_scaling.py
    from common import row

SHARD_COUNTS = (1, 2, 4)


def _measure(*, ref_len, n_reads, read_len, p_cap, candidates, reps, seed):
    """Time single-device vs sharded mapping on one seeded read batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import shard
    from repro.core import mapper, minimizer_index
    from repro.core.genasm import GenASMConfig
    from repro.genomics import encode, simulate

    cfg = GenASMConfig()
    common = dict(p_cap=p_cap, filter_bits=128, filter_k=12)
    ref = simulate.random_reference(ref_len, seed=seed)
    rs = simulate.simulate_reads(ref, n_reads=n_reads, read_len=read_len,
                                 profile=simulate.ILLUMINA, seed=seed + 1)
    arr, lens = encode.batch_reads(list(rs.reads), p_cap)
    epi = minimizer_index.build_epoched_index(ref, w=8, k=12)

    def timed(fn, ex):
        """Average batch time + per-stage seconds from ``ex.last_times``."""
        res = fn()  # compile + warm
        stages: dict[str, float] = {}
        t0 = time.perf_counter()
        for _ in range(reps):
            res = fn()
            for name, a, b, _attrs in getattr(ex, "last_times", ()):
                stages[name] = stages.get(name, 0.0) + (b - a)
        dt = (time.perf_counter() - t0) / reps
        return res, dt, {k: round(v / reps, 5) for k, v in stages.items()}

    out = {}
    for s in SHARD_COUNTS:
        if s == 1:
            jarr, jlens = jnp.asarray(arr), jnp.asarray(lens)
            # the serve path's two-stage executor (same math as a fused
            # map_batch jit) so the 1-shard row reports its
            # seed_filter/align split alongside the sharded rows'
            ex = mapper.LinearMapExecutor(
                cfg=cfg, max_candidates=candidates,
                minimizer_w=8, minimizer_k=12, backend="lax", **common)

            def call(ex=ex):
                return jax.tree_util.tree_map(
                    np.asarray, ex(epi.index, jarr, jlens))

            res, dt, stages = timed(call, ex)
            out["1"] = {
                "reads_per_s": round(n_reads / dt, 2),
                "ms_per_batch": round(dt * 1e3, 2),
                "mapped": int((res.position >= 0).sum()),
                "spmd": False,
                "stages": stages,
            }
            continue

        esi = shard.from_epoched(epi, s)
        arrays = esi.index.arrays
        kw = dict(cfg=cfg, shard_candidates=max(1, candidates // s),
                  backend="lax", **common)
        ex = shard.ShardedMapExecutor(esi.index, **kw)
        res, dt, stages = timed(lambda: ex(arrays, arr, lens), ex)

        # the pre-device-merge reference: per-shard winners synced to
        # the host, numpy lexicographic merge, align re-dispatched —
        # what the serve path did before the packed-key argmin
        def host_call():
            st = ex.stage(arrays, arr, lens)
            fd, pos, text, t_len, _win = ex.merge_host(st)
            r = ex._align(jnp.asarray(text), jnp.asarray(arr),
                          jnp.asarray(lens, jnp.int32),
                          jnp.asarray(t_len), jnp.asarray(pos),
                          jnp.asarray(fd))
            return jax.tree_util.tree_map(np.asarray, r)

        res_host, dt_host, _ = timed(host_call, None)

        ex_as = shard.ShardedMapExecutor(esi.index, align_sharded=True,
                                         **kw)
        res_as, dt_as, _ = timed(lambda: ex_as(arrays, arr, lens), ex_as)

        # double-buffered stream: batch i's align overlaps batch i+1's
        # scatter (the serve engine's pipelined mode, minus admission)
        t0 = time.perf_counter()
        pending = ex_as.start(arrays, arr, lens, timed=False)
        for _ in range(reps - 1):
            nxt = ex_as.start(arrays, arr, lens, timed=False)
            ex_as.finish(pending)
            pending = nxt
        res_pipe = ex_as.finish(pending)[0]
        dt_pipe = (time.perf_counter() - t0) / reps

        for r in (res_host, res_as, res_pipe):  # modes are re-schedulings
            assert (np.asarray(r.position) == np.asarray(res.position)).all()

        out[str(s)] = {
            "reads_per_s": round(n_reads / dt, 2),
            "reads_per_s_host_merge": round(n_reads / dt_host, 2),
            "reads_per_s_align_sharded": round(n_reads / dt_as, 2),
            "reads_per_s_pipelined": round(n_reads / dt_pipe, 2),
            "ms_per_batch": round(dt * 1e3, 2),
            "mapped": int((res.position >= 0).sum()),
            "spmd": bool(jax.device_count() >= s),
            "stages": stages,  # avg s/batch: scatter strong-scales,
        }                      # merge+align are the Amdahl floor
    base = out["1"]["reads_per_s"]
    return {
        "ref_len": ref_len, "n_reads": n_reads, "read_len": read_len,
        "p_cap": p_cap, "candidates": candidates, "reps": reps,
        "seed": seed, "devices": jax.device_count(),
        "shards": out,
        "speedup_2shards_vs_1": round(
            out["2"]["reads_per_s"] / base, 3),
        "speedup_4shards_vs_1": round(
            out["4"]["reads_per_s"] / base, 3),
        "speedup_4shards_pipelined_vs_1": round(
            out["4"]["reads_per_s_pipelined"] / base, 3),
        "device_merge_win_4shards": round(
            out["4"]["reads_per_s"] / out["4"]["reads_per_s_host_merge"],
            3),
        "pipeline_win_4shards": round(
            out["4"]["reads_per_s_pipelined"] / out["4"]["reads_per_s"],
            3),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (smaller reference, fewer reps)")
    ap.add_argument("--json", default=None, help="write summary JSON here")
    ap.add_argument("--seed", type=int, default=17)
    args = ap.parse_args(argv)

    if args.smoke:
        params = dict(ref_len=160_000, n_reads=32, read_len=100, p_cap=128,
                      candidates=64, reps=4)
        align_params = dict(ref_len=120_000, n_reads=8, read_len=350,
                            p_cap=384, candidates=8, reps=2)
    else:
        params = dict(ref_len=1_000_000, n_reads=64, read_len=100, p_cap=128,
                      candidates=64, reps=8)
        align_params = dict(ref_len=400_000, n_reads=16, read_len=450,
                            p_cap=512, candidates=8, reps=4)

    out = _measure(seed=args.seed, **params)
    # align-dominated point: long reads/caps, small candidate
    # budget — where the sharded/pipelined align stage must win
    out["align_point"] = _measure(seed=args.seed + 1, **align_params)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")

    base = out["shards"]["1"]["reads_per_s"]
    for s in SHARD_COUNTS:
        r = out["shards"][str(s)]
        row(f"shard_scaling_s{s}", 1e6 / max(r["reads_per_s"], 1e-9),
            f"reads_per_s={r['reads_per_s']};mapped={r['mapped']}/"
            f"{out['n_reads']};speedup={r['reads_per_s'] / base:.2f}x;"
            f"spmd={r['spmd']}")
    row("shard_scaling_speedup", 0.0,
        f"4shards_vs_1={out['speedup_4shards_vs_1']}x;"
        f"2shards_vs_1={out['speedup_2shards_vs_1']}x;"
        f"pipelined_4_vs_1={out['speedup_4shards_pipelined_vs_1']}x")
    row("shard_scaling_merge", 0.0,
        f"device_merge_win_4shards={out['device_merge_win_4shards']}x;"
        f"pipeline_win_4shards={out['pipeline_win_4shards']}x")
    ap4 = out["align_point"]["shards"]["4"]
    row("shard_scaling_align_point", 0.0,
        f"reads_per_s={ap4['reads_per_s']};"
        f"align_sharded={ap4['reads_per_s_align_sharded']};"
        f"pipelined={ap4['reads_per_s_pipelined']};"
        f"host_merge={ap4['reads_per_s_host_merge']}")
    return out


if __name__ == "__main__":
    main()
