"""Per-kernel roofline: predicted vs measured (EXPERIMENTS.md §Roofline).

For every registered align backend × a bucket-cap ladder, this emits one
row joining the three sides of `repro.obs.roofline`:

* **analytic** — exact DC word-ops / TB bytes / HBM traffic per
  ``align_batch`` call from the counter model (`align_counters`);
* **measured** — the compiled executable's ``cost_analysis()`` flops and
  bytes-accessed (same compile that is timed, so the numbers describe
  exactly the executable on the clock);
* **achieved** — analytic ops over min-of-iters wall time → ops/s,
  arithmetic intensity, and %-of-roof against the platform's
  `DeviceSpec`.

Two gates ride along: a **counter sanity** check (analytic vs
``cost_analysis()`` ops/bytes for the ``lax`` backend within the
documented factors — XLA's CPU flop counter ignores integer/bitwise ops
and counts scan bodies once, see DESIGN.md §13) and the **model-seeded
autotune** check (the ``block_bt`` ranked best by `predict_block_bt`
must be within 10% of the empirically autotuned best's throughput).

On CPU the Pallas rows run in interpret mode, so their *wall* numbers
measure the interpreter, not the kernel — the analytic columns are the
accelerator-relevant content there (ROADMAP item 5).  Alignment runs
distances-only (``emit_cigar=False``): the DC phase is what the counter
model covers.

    PYTHONPATH=src python benchmarks/roofline.py --smoke --json out.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import align
from repro.core.genasm import GenASMConfig
from repro.obs.roofline import (DeviceSpec, align_counters, predict_block_bt,
                                predict_time_s)

try:
    from .common import aligned_read_batch, row
except ImportError:  # script-style: python benchmarks/roofline.py
    from common import aligned_read_batch, row

BACKENDS = ("ref", "lax", "pallas_dc", "pallas_dc_v2")

# documented agreement bands for the lax backend on CPU (DESIGN.md §13):
# XLA's CPU cost model counts only float flops (the integer/bitwise DC
# ops are invisible) and counts while/scan bodies once, so analytic
# word-ops exceed measured flops by a large, version-dependent factor;
# bytes agree within a much tighter band (the TB store dominates both)
OPS_RATIO_BAND = (0.25, 256.0)
BYTES_RATIO_BAND = (1.0 / 16.0, 16.0)


def _cost_of(compiled) -> dict:
    """flops / bytes-accessed from a compiled executable (version-safe)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax: one dict per device
        ca = ca[0] if ca else {}
    return {"measured_ops": float(ca.get("flops", 0.0)),
            "measured_bytes": float(ca.get("bytes accessed", 0.0))}


def _compile_site(backend: str, cap: int, batch: int, *, cfg: GenASMConfig,
                  block_bt: int):
    """One compiled distances-only align executable + its input args."""
    # reads a touch shorter than the cap so p_cap lands exactly on the
    # ladder rung the counters were computed for
    texts, pats, p_lens, t_lens = aligned_read_batch(
        batch, cap - 8, p_cap=cap, t_extra=2 * cfg.w, seed=29)
    args = (jnp.asarray(texts), jnp.asarray(pats), jnp.asarray(p_lens),
            jnp.asarray(t_lens))
    assert int(pats.shape[1]) == cap

    def fn(t, p, pl, tl):
        return align.align_batch(t, p, pl, tl, cfg=cfg, backend=backend,
                                 p_cap=cap, emit_cigar=False,
                                 block_bt=block_bt).distance

    return jax.jit(fn).lower(*args).compile(), args


def _time_compiled(compiled, args, iters: int) -> float:
    """Min-of-iters wall seconds per call (one warmup off-clock)."""
    jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def kernel_table(caps, *, batch: int, ref_batch: int, iters: int,
                 spec: DeviceSpec, cfg: GenASMConfig) -> list[dict]:
    """One predicted-vs-measured row per (backend, bucket_cap)."""
    rows = []
    for backend in BACKENDS:
        b = ref_batch if backend == "ref" else batch
        for cap in caps:
            bt = align.block_size_for(backend, cap, cfg.k, b)
            c = align_counters(backend, cap, cfg.k, b, w=cfg.w, o=cfg.o,
                               block_bt=bt)
            compiled, args = _compile_site(backend, cap, b, cfg=cfg,
                                           block_bt=bt)
            cost = _cost_of(compiled)
            wall = _time_compiled(compiled, args, iters)
            ach = c.word_ops / wall
            roof = spec.roof_ops_per_s(c.intensity)
            r = {
                "backend": backend, "bucket_cap": cap, "batch": b,
                "block_bt": c.notes.get("block_bt"), "exact": c.exact,
                "analytic_ops": c.word_ops,
                "analytic_tb_bytes": c.tb_bytes,
                "bytes": c.hbm_bytes,
                **cost,
                "intensity": round(c.intensity, 4),
                "wall_us": round(wall * 1e6, 1),
                "predicted_us": round(predict_time_s(c, spec) * 1e6, 1),
                "achieved_ops_per_s": round(ach, 1),
                "pct_of_roof": round(ach / roof, 6) if roof else 0.0,
            }
            rows.append(r)
            row(f"roofline_{backend}_cap{cap}", r["wall_us"],
                f"analytic_ops={c.word_ops:.3g};"
                f"measured_ops={cost['measured_ops']:.3g};"
                f"bytes={c.hbm_bytes:.3g};intensity={r['intensity']};"
                f"pct_of_roof={r['pct_of_roof']:.2%};"
                f"predicted_us={r['predicted_us']}")
    return rows


def sanity_check(rows: list[dict]) -> dict:
    """Analytic vs ``cost_analysis()`` agreement for the lax backend.

    The lax backend is the one site where no interpret-mode skew
    applies: the executable XLA costed is the executable that ran.
    """
    checks = []
    for r in rows:
        if r["backend"] != "lax":
            continue
        ops_ratio = (r["analytic_ops"] / r["measured_ops"]
                     if r["measured_ops"] else float("inf"))
        bytes_ratio = (r["bytes"] / r["measured_bytes"]
                       if r["measured_bytes"] else float("inf"))
        checks.append({
            "bucket_cap": r["bucket_cap"],
            "ops_ratio": round(ops_ratio, 3),
            "bytes_ratio": round(bytes_ratio, 3),
            "ops_ok": OPS_RATIO_BAND[0] <= ops_ratio <= OPS_RATIO_BAND[1],
            "bytes_ok":
                BYTES_RATIO_BAND[0] <= bytes_ratio <= BYTES_RATIO_BAND[1],
        })
    ok = bool(checks) and all(c["ops_ok"] and c["bytes_ok"] for c in checks)
    out = {"ops_ratio_band": list(OPS_RATIO_BAND),
           "bytes_ratio_band": list(BYTES_RATIO_BAND),
           "checks": checks, "ok": ok}
    row("roofline_counter_sanity", 0.0,
        f"ok={ok};n_checks={len(checks)}")
    return out


def autotune_check(*, cap: int, batch: int, candidates, iters: int,
                   spec: DeviceSpec, cfg: GenASMConfig) -> dict:
    """Model-seeded vs empirical block-size pick, throughput-compared.

    Runs the empirical `align.autotune` search and the zero-measurement
    `predict_block_bt` ranking over the same candidate set, then times
    both winners; ``within_10pct`` is the ISSUE acceptance bound.
    """
    backend = "pallas_dc"
    emp_bt = align.autotune(backend, cap, cfg.k, batch=batch,
                            candidates=candidates, cfg=cfg, iters=iters)
    model_bt = predict_block_bt(backend, cap, cfg.k, batch, spec=spec,
                                candidates=candidates, w=cfg.w, o=cfg.o)
    if emp_bt == model_bt:
        ratio = 1.0
        emp_s = model_s = None
    else:
        c1, a1 = _compile_site(backend, cap, batch, cfg=cfg,
                               block_bt=emp_bt)
        c2, a2 = _compile_site(backend, cap, batch, cfg=cfg,
                               block_bt=model_bt)
        emp_s = _time_compiled(c1, a1, iters)
        model_s = _time_compiled(c2, a2, iters)
        ratio = emp_s / model_s  # >1: model pick is faster than empirical
    out = {"bucket_cap": cap, "batch": batch, "candidates": list(candidates),
           "empirical_bt": emp_bt, "model_bt": model_bt,
           "empirical_s": emp_s, "model_s": model_s,
           "model_vs_empirical": round(ratio, 4),
           "within_10pct": ratio >= 0.9}
    row("roofline_autotune_model", 0.0,
        f"empirical_bt={emp_bt};model_bt={model_bt};"
        f"model_vs_empirical={out['model_vs_empirical']};"
        f"within_10pct={out['within_10pct']}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small caps/batches, 1 timed iter)")
    ap.add_argument("--json", default=None, help="write summary JSON here")
    args = ap.parse_args(argv)

    if args.smoke:
        caps, batch, ref_batch, iters = (96, 160, 320), 8, 4, 1
        at = dict(cap=160, batch=16, candidates=(8, 16), iters=1)
    else:
        caps, batch, ref_batch, iters = (160, 320, 640), 16, 4, 2
        at = dict(cap=320, batch=64, candidates=(16, 32, 64), iters=2)

    cfg = GenASMConfig()
    spec = DeviceSpec.for_device()
    align.clear_autotune_cache()  # heuristic block sizes, reproducible rows
    rows = kernel_table(caps, batch=batch, ref_batch=ref_batch, iters=iters,
                        spec=spec, cfg=cfg)
    out = {
        "platform": jax.default_backend(),
        "interpret_pallas": align.needs_interpret(),
        "device_spec": spec.name,
        "caps": list(caps),
        "kernels": rows,
        "sanity": sanity_check(rows),
        "autotune": autotune_check(spec=spec, cfg=cfg, **at),
    }
    align.clear_autotune_cache()  # don't leak the tuned site to other mods
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
    return out


if __name__ == "__main__":
    main()
