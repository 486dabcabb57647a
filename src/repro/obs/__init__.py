"""repro.obs — dependency-free tracing, attribution, and exposition.

The dissertation's method is *characterize, then co-design*: every
accelerator decision in the source papers starts from a per-phase
breakdown of where wall time goes.  This package produces ours
automatically on every serving and benchmark run (DESIGN.md §12):

* `trace` — thread-safe monotonic-clock `Span`/`Tracer` with parent
  links and per-span attributes, a ring-buffer `TraceLog`, Chrome/
  Perfetto ``trace_event`` JSON export, a structured JSONL sink, and
  ``jax.profiler`` annotations of the same names (JAX imported lazily).
* `attrib` — folds finished spans into a per-stage wall-time ledger
  (enqueue-wait → dispatch → encode → seed/filter → graph prefilter →
  DC filter → shard scatter → host merge → align → fetch → emit →
  trace replay) and renders the Amdahl report:
  serial fraction, per-stage p50/p99, projected speedup from sharding
  each stage.
* `http` — stdlib exposition endpoint serving ``/metrics`` (the
  engine's `Metrics.render()`), ``/healthz``, ``/trace`` (last-N
  spans), ``/attrib`` (the live Amdahl report), and ``/roofline``
  (the per-kernel roofline table).
* `roofline` — kernel-level roofline layer (DESIGN.md §13): exact
  analytic op/byte counters per align-kernel launch, pluggable JSON
  `DeviceSpec` roofline targets, XLA ``cost_analysis()`` cross-checks,
  and the analytic block-size model behind
  ``REPRO_ALIGN_AUTOTUNE=model``.

Stdlib-only at import by design: it must import (and stay cheap) in
every environment the serving path runs in, kernels or not — the
roofline module's measured side and the tracer's profiler annotations
lazy-import `jax` only when used.
"""
from .attrib import (AttributionReport, StageLedger, build_ledger,
                     render_report)
from .http import ObsServer
from .roofline import (DeviceSpec, KernelCounters, RooflineManager,
                       align_counters, dc_window_counters, predict_block_bt)
from .trace import NULL_TRACER, Span, StageTimer, TraceLog, Tracer

__all__ = [
    "Span", "Tracer", "TraceLog", "StageTimer", "NULL_TRACER",
    "StageLedger", "AttributionReport", "build_ledger", "render_report",
    "ObsServer",
    "DeviceSpec", "KernelCounters", "RooflineManager", "align_counters",
    "dc_window_counters", "predict_block_bt",
]
