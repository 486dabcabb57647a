"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  read_alignment   — Figs 4-8/4-9 (GenASM vs DP alignment kernels)
  prealign_filter  — §4.10.3 (GenASM-DC filter vs q-gram approx, accuracy)
  edit_distance    — Fig 4-13 (GenASM vs Myers/Edlib)
  bitalign         — Fig 6-15 (BitAlign vs graph-DP / PaSGAL stand-in)
  segram_e2e       — Figs 6-11..6-14 (SeGraM mapping on repro.graph)
  graph_serve      — graph vs linear serving throughput (Poisson)
  kernel_dc        — Ch. 5 BitMAc kernel analysis
  align_dispatch   — repro.align backend dispatch (lax vs pallas_dc*)
  serve_engine     — micro-batching engine under Poisson arrivals
  shard_scaling    — reads/s vs 1/2/4 reference shards (repro.shard)
  roofline         — per-kernel predicted-vs-measured roofline table
                     (§Roofline: all align backends × bucket caps)

``--smoke`` runs the CI-sized subset (align_dispatch + serve_engine +
segram_e2e + graph_serve + shard_scaling + roofline) and ``--json PATH``
writes
their summaries into one artifact; the serving modules also emit their
per-stage Amdahl attribution (`repro.obs`) into the summary and, under
``--smoke``, Perfetto traces (``trace_serve_engine.json``,
``trace_graph_serve_{linear,graph}.json`` — CI uploads them):

    PYTHONPATH=src python benchmarks/run.py --smoke --json bench_summary.json
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys

if __package__ in (None, ""):  # script-style: python benchmarks/run.py
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    __package__ = "benchmarks"

# modules with a --smoke flag and a summary-dict return (the CI subset)
SMOKE_MODS = ("align_dispatch", "serve_engine", "segram_e2e", "graph_serve",
              "shard_scaling", "roofline")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("only", nargs="?", default=None,
                    help="run a single module by name")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized subset (align_dispatch + serve_engine)")
    ap.add_argument("--json", default=None,
                    help="write collected module summaries here")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (align_dispatch, bitalign, edit_distance, graph_serve,
                   kernel_dc, prealign_filter, read_alignment, roofline,
                   segram_e2e, serve_engine, shard_scaling)

    mods = {
        "read_alignment": read_alignment,
        "prealign_filter": prealign_filter,
        "edit_distance": edit_distance,
        "bitalign": bitalign,
        "segram_e2e": segram_e2e,
        "graph_serve": graph_serve,
        "kernel_dc": kernel_dc,
        "align_dispatch": align_dispatch,
        "serve_engine": serve_engine,
        "shard_scaling": shard_scaling,
        "roofline": roofline,
    }
    summaries: dict[str, object] = {}
    print("name,us_per_call,derived")
    for name, mod in mods.items():
        if args.only and name != args.only:
            continue
        if args.smoke and name not in SMOKE_MODS:
            continue
        try:
            # modules with an argv parameter parse CLI flags; hand them an
            # empty argv so the harness's own arguments don't reach argparse
            if "argv" in inspect.signature(mod.main).parameters:
                sub = ["--smoke"] if args.smoke and name in SMOKE_MODS \
                    else []
                if args.smoke and name in ("serve_engine", "graph_serve"):
                    # smoke artifacts: Perfetto traces next to the JSON
                    sub += ["--trace-out", f"trace_{name}.json"]
                if args.smoke and name == "roofline":
                    # standalone table artifact (CI uploads it)
                    sub += ["--json", "roofline_table.json"]
                out = mod.main(sub)
            else:
                out = mod.main()
            if isinstance(out, dict):
                summaries[name] = out
        except Exception as e:  # run the rest, fail the run at the end
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
            summaries[name] = {"error": f"{type(e).__name__}: {e}"}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summaries, f, indent=2)
        print(f"wrote {args.json}")
    errors = [n for n, s in summaries.items()
              if isinstance(s, dict) and "error" in s]
    if errors:
        # a failed module fails the run, not ship an error artifact
        sys.exit(f"benchmark(s) failed: {', '.join(errors)}")


if __name__ == "__main__":
    main()
