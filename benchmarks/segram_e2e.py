"""Paper Figs 6-11..6-14: SeGraM end-to-end sequence-to-graph mapping
throughput (reads/s), short and long-ish reads.

Ported onto the `repro.graph` subsystem (PR 4): tiled graph index +
`graph.mapper.map_batch` through the `repro.align` dispatch — the same
path the serve engine compiles — instead of the old per-read vmap of
per-candidate whole-window scans in `core/segram/segram.py`.

    PYTHONPATH=src python benchmarks/segram_e2e.py            # full
    PYTHONPATH=src python benchmarks/segram_e2e.py --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import json

import jax.numpy as jnp
import numpy as np

from repro.core.genasm import GenASMConfig
from repro.graph import index as gindex
from repro.graph import mapper as gmapper
from repro.genomics import encode, simulate

try:
    from .common import row, timeit
except ImportError:  # script-style: python benchmarks/segram_e2e.py
    from common import row, timeit


def run(kind: str = "short", batch: int = 16, *, ref_len: int = 8000,
        backend: str | None = None):
    ref = simulate.random_reference(ref_len, seed=21)
    variants = simulate.simulate_variants(
        ref, n_snp=ref_len // 333, n_ins=ref_len // 1000,
        n_del=ref_len // 1000, seed=4)
    cfg = GenASMConfig()
    if kind == "short":
        read_len, p_cap = 100, 128
        prof = simulate.ILLUMINA
    else:
        read_len, p_cap = 400, 448
        prof = simulate.PACBIO_CLR
    idx = gindex.build_graph_index(ref, variants, w=8, k=12,
                                   window=p_cap + 2 * cfg.w)
    rs = simulate.simulate_reads(ref, n_reads=batch, read_len=read_len,
                                 profile=prof, seed=5)
    reads, lens = encode.batch_reads(rs.reads, p_cap)
    filter_k = max(12, int(128 * (prof.error_rate + 0.05)))

    be = gmapper.graph_backend_name(backend)

    # map_batch is host-orchestrated (prefilter → rung sync → DC → align),
    # so it is timed eagerly — its stages jit themselves internally
    def f(r, l):
        return gmapper.map_batch(
            idx.arrays, r, l, geometry=idx.geometry, cfg=cfg, p_cap=p_cap,
            filter_bits=128, filter_k=filter_k, minimizer_w=8, minimizer_k=12,
            backend=be)
    us = timeit(f, jnp.asarray(reads), jnp.asarray(lens))
    out = f(jnp.asarray(reads), jnp.asarray(lens))
    mapped = int(np.sum(~np.asarray(out.failed)))
    reads_per_s = batch / (us / 1e6)
    row(f"segram_e2e_{kind}", us / batch,
        f"reads_per_s={reads_per_s:.1f};mapped={mapped}/{batch};backend={be}")
    return {"read_len": read_len, "backend": be,
            "reads_per_s": round(reads_per_s, 2), "mapped": mapped,
            "n_reads": batch}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small ref, short reads only)")
    ap.add_argument("--json", default=None, help="write summary JSON here")
    ap.add_argument("--backend", default=None,
                    help="repro.align backend (graph twin resolved)")
    args = ap.parse_args(argv)

    if args.smoke:
        out = {"short": run("short", batch=8, ref_len=4000,
                            backend=args.backend)}
    else:
        out = {"short": run("short", backend=args.backend),
               "long": run("long", batch=8, backend=args.backend)}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
    return out


if __name__ == "__main__":
    main()
