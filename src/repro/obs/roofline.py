"""Kernel-level roofline observability (DESIGN.md §13).

PR 7's tracing plane says *where wall time goes* per stage; this module
says *whether each kernel is fast for the hardware it runs on*.  GenASM's
DC and traceback phases have exact, analytically countable work — bit-
vector word-ops per (text step, distance row, word) and TB-store bytes
per window — so every align-kernel launch gets three numbers:

* **analytic** — exact per-launch counters (`align_counters`) as a pure
  function of ``(backend, bucket_cap, k, batch, w, o, block_bt)``.  The
  per-window terms are the ones already measured in EXPERIMENTS perf
  #3/#14 (``w·(k+1)·6·nw`` word-ops, ``w·(k+1)·3·nw·4`` TB bytes for the
  M/I/D store, ``(w+1)·(k+1)·nw·4`` for the v2 R-only store).  Exact for
  our code; responds to block-size and ladder changes.
* **measured** — ``jax.jit(...).lower(...).compile().cost_analysis()``
  flops / bytes-accessed per compiled ``(backend, cap)`` executor.
  CAVEATS (verified on the CPU backend, same class of skew as
  `launch/roofline.py`): XLA counts a ``while``/scan body ONCE, so the
  window loop undercounts by ~``n_windows``; and the CPU flop counter
  ignores integer/bitwise ops, so ``flops`` sees only the float residue
  of an integer-dominated program.  The sanity gate therefore checks
  order-of-magnitude agreement (documented factors in DESIGN.md §13),
  not precision.
* **achieved** — analytic ops over the wall-clock seconds the tracing
  plane already collects (executor ``last_times`` align intervals),
  yielding ops/s, bytes/s, arithmetic intensity, and %-of-roof against a
  pluggable :class:`DeviceSpec` (JSON files under ``device_specs/``:
  ``tpu_v5e``, ``gpu_generic``, ``cpu_host`` — the hardcoded v5e
  constants of `launch/roofline.py` live there now).

The same analytic model seeds the block-size autotune cache
(`repro.align.api`, ``REPRO_ALIGN_AUTOTUNE=model``): predicted launch
cost ``launches·overhead + max(ops/peak, bytes/bw)`` ranks candidate
``block_bt`` values with zero on-device search.

Stdlib-only at import time (the `repro.obs` contract): `jax` and
`repro.align` are imported lazily inside the measured-side helpers.
"""
from __future__ import annotations

import json
import threading
from dataclasses import dataclass, field
from pathlib import Path

SPEC_DIR = Path(__file__).with_name("device_specs")
# JAX ``device_kind`` -> bundled spec (`DeviceSpec.for_device`)
SPEC_OF_KIND = {"TPU v5 lite": "tpu_v5e", "cpu": "cpu_host"}

# mirrors repro.core.bitvector.WORD_BITS without importing jax-adjacent code
WORD_BITS = 32
# word-ops per (text step, distance row, word) of the DC recurrence:
# three shl1 (shift+carry-or counts as 2) feed one 3-way AND chain —
# ~6 uint32 ops per cell, the accounting perf #3 established
DC_OPS_PER_CELL = 6
# the paper's TB store streams 3 intermediate bitvectors (M, I, D)
TB_VECTORS_V1 = 3


# ---------------------------------------------------------------- specs ----
@dataclass(frozen=True)
class DeviceSpec:
    """Roofline targets of one device, loaded from a JSON spec file.

    ``peak_flops`` is the dense-matmul peak (bf16 FMA/s — the LM
    roofline in `launch/roofline.py` divides by it); ``peak_word_ops``
    is the 32-bit integer/logical throughput of the vector unit, the
    peak the bit-parallel GenASM kernels can actually reach;
    ``launch_overhead_s`` is the fixed per-kernel-launch cost the
    block-size model amortizes.
    """

    name: str
    peak_flops: float
    peak_word_ops: float
    hbm_bw: float
    link_bw: float = 0.0
    launch_overhead_s: float = 0.0
    description: str = ""

    @classmethod
    def from_json(cls, path: str | Path) -> "DeviceSpec":
        """Load a spec file (unknown keys are ignored, future-proof)."""
        raw = json.loads(Path(path).read_text())
        kw = {k: raw[k] for k in
              ("name", "peak_flops", "peak_word_ops", "hbm_bw", "link_bw",
               "launch_overhead_s", "description") if k in raw}
        return cls(**kw)

    @classmethod
    def load(cls, name: str | Path) -> "DeviceSpec":
        """Bundled spec by name (``tpu_v5e``/``gpu_generic``/``cpu_host``)
        or any explicit ``*.json`` path."""
        p = Path(name)
        if p.suffix == ".json" and p.exists():
            return cls.from_json(p)
        bundled = SPEC_DIR / f"{name}.json"
        if not bundled.exists():
            known = sorted(f.stem for f in SPEC_DIR.glob("*.json"))
            raise ValueError(f"unknown device spec {name!r}; bundled: {known}")
        return cls.from_json(bundled)

    @classmethod
    def for_device(cls, kind: str | None = None) -> "DeviceSpec":
        """Spec for a JAX ``device_kind`` (default: the first device's).

        An unknown kind is an error, never a default: a roofline share
        against the wrong chip's peaks is a wrong number.
        """
        if kind is None:
            import jax

            kind = jax.devices()[0].device_kind
        try:
            return cls.load(SPEC_OF_KIND[kind])
        except KeyError:
            raise ValueError(
                f"no device spec for device_kind {kind!r}; known kinds: "
                f"{sorted(SPEC_OF_KIND)}") from None

    def roof_ops_per_s(self, intensity: float) -> float:
        """Attainable word-ops/s at ``intensity`` (ops/HBM byte)."""
        return min(self.peak_word_ops, max(intensity, 0.0) * self.hbm_bw)


# ------------------------------------------------------- analytic model ----
@dataclass(frozen=True)
class KernelCounters:
    """Exact per-``align_batch``-call work of one dispatch site."""

    word_ops: float  # uint32 ops across all launches of one call
    tb_bytes: float  # TB-store stream (the ASIC's TB-SRAM traffic)
    hbm_bytes: float  # total device-memory traffic (inputs+outputs+TB)
    launches: int  # kernel grid launches per call
    exact: bool = True  # False for the ref oracle's DP-cell estimate
    notes: dict = field(default_factory=dict)

    @property
    def intensity(self) -> float:
        """Arithmetic intensity: word-ops per HBM byte."""
        return self.word_ops / self.hbm_bytes if self.hbm_bytes else 0.0


def n_windows(bucket_cap: int, *, w: int = 64, o: int = 24) -> int:
    """Window steps of one aligned read at ``bucket_cap`` (cfg.n_windows)."""
    return -(-bucket_cap // (w - o)) + 2


def dc_window_counters(w: int, k: int, *, store: str = "mid") -> dict:
    """Hand-checkable per-lane, per-window DC terms.

    ``store`` selects the TB layout: ``"mid"`` (M/I/D, paper-faithful —
    the v1 kernel and the lax backend, which materializes the same
    store) or ``"r"`` (v2 R-only rows, perf #3).
    """
    if w % WORD_BITS:
        raise ValueError(f"w must be a multiple of {WORD_BITS}, got {w}")
    nw = w // WORD_BITS
    word_ops = w * (k + 1) * DC_OPS_PER_CELL * nw
    if store == "mid":
        tb_bytes = w * (k + 1) * TB_VECTORS_V1 * nw * 4
    elif store == "r":
        tb_bytes = (w + 1) * (k + 1) * nw * 4  # incl. the i=w boundary row
    else:
        raise ValueError(f"store must be 'mid' or 'r', got {store!r}")
    return {"word_ops": word_ops, "tb_bytes": tb_bytes, "nw": nw}


def effective_block(block_bt: int | None, batch: int) -> int:
    """The batch tile the kernels actually launch with
    (`repro.kernels.genasm_dc.batch_tile` on the current platform)."""
    from repro.align.api import needs_interpret
    from repro.kernels.genasm_dc import batch_tile

    return batch_tile(block_bt, batch, interpret=needs_interpret())


_STORE_OF = {"lax": "mid", "pallas_dc": "mid", "pallas_dc_v2": "r"}


def align_counters(backend: str, bucket_cap: int, k: int, batch: int, *,
                   w: int = 64, o: int = 24,
                   block_bt: int | None = None) -> KernelCounters:
    """Exact analytic counters for one ``align_batch`` call at a site.

    Padded lanes execute (the driver pads the batch up to a ``block_bt``
    multiple), so they count; distances-only vs CIGAR does not change DC
    work.  The ``ref`` oracle has no kernel — it gets a DP-cell estimate
    (1 op + ~2 bytes per cell) flagged ``exact=False``.
    """
    nwin = n_windows(bucket_cap, w=w, o=o)
    if backend == "ref":
        t_cap = bucket_cap + 2 * w
        cells = float(batch) * bucket_cap * t_cap
        return KernelCounters(
            word_ops=cells, tb_bytes=0.0, hbm_bytes=2.0 * cells, launches=0,
            exact=False, notes={"model": "dp_cells", "n_windows": nwin})
    store = _STORE_OF.get(backend)
    if store is None:
        raise KeyError(f"no analytic counter model for backend {backend!r}")
    per = dc_window_counters(w, k, store=store)
    if backend == "lax":
        bt, b_pad = batch, batch  # vmap over the full batch, one launch/step
    else:
        bt = effective_block(block_bt, batch)
        b_pad = -(-batch // bt) * bt
    launches = nwin * (b_pad // bt if bt else 1)
    lanes = nwin * b_pad  # window executions across the whole call
    word_ops = float(lanes) * per["word_ops"]
    tb_bytes = float(lanes) * per["tb_bytes"]
    # per window step: read text+pattern tiles (int8), write d_min (int32)
    # and stream the TB store to device memory
    io_bytes = float(nwin) * b_pad * (2 * w + 4)
    return KernelCounters(
        word_ops=word_ops, tb_bytes=tb_bytes, hbm_bytes=io_bytes + tb_bytes,
        launches=launches,
        notes={"n_windows": nwin, "block_bt": bt, "batch_padded": b_pad,
               "store": store})


def predict_time_s(c: KernelCounters, spec: DeviceSpec) -> float:
    """Model time of one call: launch overhead + the binding roof term."""
    roof = max(c.word_ops / spec.peak_word_ops,
               c.hbm_bytes / spec.hbm_bw if spec.hbm_bw else 0.0)
    return c.launches * spec.launch_overhead_s + roof


def predict_block_bt(backend: str, bucket_cap: int, k: int, batch: int, *,
                     spec: DeviceSpec | None = None,
                     candidates: tuple[int, ...] = (8, 16, 32, 64, 128, 256),
                     w: int = 64, o: int = 24) -> int:
    """Model-predicted best batch tile for a dispatch site.

    Ranks each candidate by :func:`predict_time_s` — padding waste grows
    the op/byte terms, small tiles grow the launch term — preferring the
    larger tile on ties (fewer launches never hurts the model).  No
    device work: this is what ``REPRO_ALIGN_AUTOTUNE=model`` calls.
    """
    spec = spec or DeviceSpec.for_device()
    best_bt, best_t = None, float("inf")
    for bt in sorted(set(effective_block(c, batch) for c in candidates)):
        t = predict_time_s(
            align_counters(backend, bucket_cap, k, batch,
                           w=w, o=o, block_bt=bt), spec)
        if t < best_t or (t == best_t and best_bt is not None
                          and bt > best_bt):
            best_bt, best_t = bt, t
    return best_bt or effective_block(None, batch)


# -------------------------------------------------------- measured side ----
def measured_align_cost(backend: str, bucket_cap: int, k: int, batch: int, *,
                        block_bt: int | None = None) -> dict:
    """Compiled-executor ``cost_analysis()`` for one dispatch site.

    Lowers + compiles the backend fn on synthetic input at the site's
    signature (distances-only — DC work is what the model counts) and
    returns ``{"measured_ops", "measured_bytes"}``.  See the module
    docstring for the documented skews on CPU.  Raises whatever the
    lowering raises; callers that must not fail wrap this.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.align.api import get_backend, needs_interpret
    from repro.core.genasm import GenASMConfig

    be = get_backend(backend)
    cfg = GenASMConfig(k=k, o=min(k, 24) or 8)
    rng = np.random.default_rng(0xB10C)
    texts = jnp.asarray(
        rng.integers(0, 4, size=(batch, bucket_cap + 2 * cfg.w)), jnp.int8)
    pats = jnp.asarray(
        rng.integers(0, 4, size=(batch, bucket_cap)), jnp.int8)
    p_lens = jnp.full((batch,), bucket_cap, jnp.int32)
    t_lens = jnp.full((batch,), bucket_cap + 2 * cfg.w, jnp.int32)
    bt = effective_block(block_bt, batch)

    def fn(t, p, pl, tl):
        return be.fn(t, p, pl, tl, cfg=cfg, p_cap=bucket_cap,
                     emit_cigar=False, block_bt=bt,
                     interpret=needs_interpret()).distance

    ca = jax.jit(fn).lower(texts, pats, p_lens, t_lens).compile() \
        .cost_analysis()
    if isinstance(ca, (list, tuple)):  # jax<0.4.40 returns one dict/device
        ca = ca[0] if ca else {}
    return {"measured_ops": float(ca.get("flops", 0.0)),
            "measured_bytes": float(ca.get("bytes accessed", 0.0))}


# ------------------------------------------------------------- manager ----
@dataclass
class _Site:
    """One ``(backend, bucket_cap, k, batch, block_bt)`` dispatch site."""

    backend: str
    bucket_cap: int
    k: int
    batch: int
    block_bt: int | None
    counters: KernelCounters
    calls: int = 0
    align_s: float = 0.0
    measured: dict | None = None  # cost_analysis cache (or {"error": ...})

    @property
    def key(self) -> str:
        return f"{self.backend}/cap{self.bucket_cap}"


class RooflineManager:
    """Per-process registry of align-kernel dispatch sites (snippet-1 shape).

    The serve engine calls :meth:`record_flush` after every linear-
    workload flush with the align stage's wall interval; the manager
    folds in the site's analytic counters, increments the per-kernel
    `Metrics` counters (``kernel_<backend>_cap<cap>_word_ops`` /
    ``_tb_bytes`` / ``_hbm_bytes`` / ``_launches`` / ``_align_s``), and
    emits a Perfetto ``"C"`` counter sample through the bound tracer.
    :meth:`report` is the ``/roofline`` payload: one row per site with
    analytic, measured (lazy ``cost_analysis()``, cached), and achieved
    terms against the device spec.  ``enabled=False`` makes
    ``record_flush`` a no-op (the A/B switch the overhead benchmark
    toggles).
    """

    def __init__(self, spec: DeviceSpec | None = None, *, metrics=None,
                 tracer=None, enabled: bool = True,
                 measure: bool = True) -> None:
        self.spec = spec or DeviceSpec.for_device()
        self.metrics = metrics
        self.tracer = tracer
        self.enabled = enabled
        self.measure = measure  # allow cost_analysis compiles from report()
        self._sites: dict[tuple, _Site] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------ record --
    def site(self, backend: str, bucket_cap: int, k: int, batch: int,
             block_bt: int | None = None) -> _Site | None:
        """Get-or-register a dispatch site (None if unmodelable)."""
        key = (backend, bucket_cap, k, batch, block_bt)
        with self._lock:
            s = self._sites.get(key)
            if s is None:
                try:
                    c = align_counters(backend, bucket_cap, k, batch,
                                       block_bt=block_bt)
                except KeyError:  # graph/unknown backends: no model yet
                    return None
                s = self._sites[key] = _Site(
                    backend=backend, bucket_cap=bucket_cap, k=k, batch=batch,
                    block_bt=block_bt, counters=c)
            return s

    def record_flush(self, backend: str, bucket_cap: int, k: int, batch: int,
                     *, align_s: float | None,
                     block_bt: int | None = None) -> KernelCounters | None:
        """Fold one flush's align launch into the site's running totals."""
        if not self.enabled:
            return None
        s = self.site(backend, bucket_cap, k, batch, block_bt)
        if s is None:
            return None
        c = s.counters
        with self._lock:
            s.calls += 1
            if align_s is not None:
                s.align_s += max(align_s, 0.0)
            cum_ops, cum_bytes = c.word_ops * s.calls, c.hbm_bytes * s.calls
        if self.metrics is not None:
            pre = f"kernel_{backend}_cap{bucket_cap}"
            self.metrics.counter(f"{pre}_word_ops").inc(c.word_ops)
            self.metrics.counter(f"{pre}_tb_bytes").inc(c.tb_bytes)
            self.metrics.counter(f"{pre}_hbm_bytes").inc(c.hbm_bytes)
            self.metrics.counter(f"{pre}_launches").inc(c.launches)
            if align_s is not None:
                self.metrics.counter(f"{pre}_align_s").inc(max(align_s, 0.0))
        if self.tracer is not None and self.tracer.enabled:
            self.tracer.counter(f"kernel/{s.key}", word_ops=cum_ops,
                                hbm_bytes=cum_bytes)
        return c

    # ------------------------------------------------------------ report --
    def _measure_site(self, s: _Site) -> dict | None:
        if s.measured is None and self.measure:
            try:
                s.measured = measured_align_cost(
                    s.backend, s.bucket_cap, s.k, s.batch,
                    block_bt=s.block_bt)
            except Exception as e:  # keep /roofline alive on exotic backends
                s.measured = {"error": f"{type(e).__name__}: {e}"}
        return s.measured

    def report(self, *, measure: bool | None = None) -> dict:
        """The ``/roofline`` payload: one row per compiled dispatch site."""
        with self._lock:
            sites = list(self._sites.values())
        rows = []
        for s in sites:
            c = s.counters
            m = self._measure_site(s) if (measure if measure is not None
                                          else self.measure) else s.measured
            m = m or {}
            ach_ops = c.word_ops * s.calls / s.align_s if s.align_s else 0.0
            ach_bytes = c.hbm_bytes * s.calls / s.align_s if s.align_s else 0.0
            roof = self.spec.roof_ops_per_s(c.intensity)
            rows.append({
                "kernel": s.key,
                "backend": s.backend, "bucket_cap": s.bucket_cap,
                "k": s.k, "batch": s.batch,
                "block_bt": c.notes.get("block_bt"),
                "launches_per_call": c.launches, "calls": s.calls,
                "exact": c.exact,
                "analytic_ops": c.word_ops,
                "analytic_tb_bytes": c.tb_bytes,
                "bytes": c.hbm_bytes,
                "measured_ops": m.get("measured_ops"),
                "measured_bytes": m.get("measured_bytes"),
                "measure_error": m.get("error"),
                "intensity": round(c.intensity, 4),
                "align_s": round(s.align_s, 6),
                "achieved_ops_per_s": ach_ops,
                "achieved_bytes_per_s": ach_bytes,
                "pct_of_roof": round(ach_ops / roof, 6) if roof else 0.0,
            })
        rows.sort(key=lambda r: (r["backend"], r["bucket_cap"]))
        return {"device_spec": {
                    "name": self.spec.name,
                    "peak_word_ops": self.spec.peak_word_ops,
                    "peak_flops": self.spec.peak_flops,
                    "hbm_bw": self.spec.hbm_bw,
                    "link_bw": self.spec.link_bw,
                    "launch_overhead_s": self.spec.launch_overhead_s},
                "kernels": rows}
