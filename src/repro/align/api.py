"""Backend registry + unified ``align_batch`` dispatch (DESIGN.md §9).

Every consumer of windowed GenASM alignment — `core/mapper.py`, the
serve engine, `genomics/pipeline.py`, `launch/serve_genomics.py`, the
benchmarks — calls :func:`align_batch` and names a backend (or lets
:func:`resolve_backend` pick one).  Adding a kernel to the system is a
registry entry plus a conformance-suite run (`tests/test_align_conformance.py`),
not another hand-wired call path.

Backends registered by `repro.align.backends`:

  ``ref``           host numpy DP oracle (exact, jit-safe via pure_callback)
  ``lax``           pure-`jax.lax` windowed aligner (`core/genasm.align`)
  ``pallas_dc``     Pallas GenASM-DC kernel, M/I/D TB store (paper-faithful)
  ``pallas_dc_v2``  Pallas kernel with R-only TB store (3× less TB traffic)

Platform handling: the Pallas kernels lower natively on TPU/GPU; on CPU
they would die with an opaque Mosaic lowering error, so dispatch passes
``interpret=True`` there — the kernel body runs as traced JAX ops with
identical semantics.  ``backend=None``/``"auto"`` resolves to the
``REPRO_ALIGN_BACKEND`` env var when set, else Pallas on an accelerator
and ``lax`` on CPU.

Block-size autotune: the kernels' batch tile ``block_bt`` trades launch
count against padding waste.  ``align_batch`` consults a per-process
cache keyed ``(backend, bucket_cap, k)``; misses fall back to a
heuristic, measure candidates on synthetic input when
``REPRO_ALIGN_AUTOTUNE=1`` (or via an explicit :func:`autotune` call),
or — ``REPRO_ALIGN_AUTOTUNE=model`` — are seeded from the analytic
roofline cost model (`repro.obs.roofline.predict_block_bt`) with zero
on-device search, via :func:`model_seed`.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.genasm import AlignResult, GenASMConfig

DEFAULT_BT = 128
_PALLAS_NATIVE = ("tpu", "gpu")


@dataclass(frozen=True)
class Backend:
    """One registered alignment implementation."""

    name: str
    fn: Callable  # (texts, patterns, p_lens, t_lens, *, cfg, p_cap,
    #               emit_cigar, block_bt, interpret) -> AlignResult
    uses_pallas: bool = False
    description: str = ""


_REGISTRY: dict[str, Backend] = {}


def register_backend(name: str, fn: Callable, *, uses_pallas: bool = False,
                     description: str = "") -> Backend:
    """Register (or replace) a backend under ``name``."""
    b = Backend(name=name, fn=fn, uses_pallas=uses_pallas,
                description=description)
    _REGISTRY[name] = b
    return b


def available_backends() -> tuple[str, ...]:
    """Names of every registered alignment backend, registration order."""
    return tuple(_REGISTRY)


def get_backend(name: str) -> Backend:
    """Registered :class:`Backend` for ``name`` (ValueError if unknown)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown align backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def needs_interpret(platform: str | None = None) -> bool:
    """True when Pallas must run in interpret mode (no native lowering)."""
    p = platform or jax.default_backend()
    return p not in _PALLAS_NATIVE


def resolve_backend(backend: str | None = None) -> Backend:
    """Map a requested name (or None/"auto") to a registered backend.

    Order: explicit name > ``REPRO_ALIGN_BACKEND`` env var > platform
    default (``pallas_dc`` on TPU/GPU, ``lax`` on CPU).
    """
    if backend in (None, "auto"):
        backend = os.environ.get("REPRO_ALIGN_BACKEND") or (
            "lax" if needs_interpret() else "pallas_dc")
    return get_backend(backend)


# ----------------------------------------------------------- autotune ----
_BLOCK_CACHE: dict[tuple[str, int, int], int] = {}


def _heuristic_block(batch: int) -> int:
    from repro.kernels.genasm_dc import batch_tile

    return batch_tile(DEFAULT_BT, batch, interpret=needs_interpret())


def block_size_for(backend: str, bucket_cap: int, k: int, batch: int) -> int:
    """Cached/heuristic batch-tile size for a dispatch site."""
    got = _BLOCK_CACHE.get((backend, bucket_cap, k))
    if got is not None:
        return got
    return _heuristic_block(batch)


def autotune(backend: str, bucket_cap: int, k: int, *,
             batch: int = 64, candidates: tuple[int, ...] = (16, 64, 128),
             cfg: GenASMConfig | None = None, iters: int = 2) -> int:
    """Measure candidate ``block_bt`` values and cache the fastest.

    Synthetic input (fixed seed) at the site's ``(bucket_cap, k)``; the
    winner lands in the process-wide cache consulted by
    :func:`block_size_for`.  Returns the chosen block size.
    """
    be = get_backend(backend)
    if not be.uses_pallas:  # nothing to tune; pin the heuristic
        _BLOCK_CACHE[(backend, bucket_cap, k)] = _heuristic_block(batch)
        return _BLOCK_CACHE[(backend, bucket_cap, k)]
    cfg = cfg or GenASMConfig(k=k, o=min(k, 24) or 8)
    rng = np.random.default_rng(0xB10C)
    texts = jnp.asarray(
        rng.integers(0, 4, size=(batch, bucket_cap + 2 * cfg.w)), jnp.int8)
    pats = jnp.asarray(rng.integers(0, 4, size=(batch, bucket_cap)), jnp.int8)
    p_lens = jnp.full((batch,), bucket_cap, jnp.int32)
    t_lens = jnp.full((batch,), bucket_cap + 2 * cfg.w, jnp.int32)
    best_bt, best_t = None, float("inf")
    for bt in candidates:
        if bt > batch:
            continue
        fn = lambda: be.fn(texts, pats, p_lens, t_lens, cfg=cfg,
                           p_cap=bucket_cap, emit_cigar=False, block_bt=bt,
                           interpret=needs_interpret())
        jax.block_until_ready(fn().distance)  # compile off-clock
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn().distance)
            ts.append(time.perf_counter() - t0)
        t = min(ts)
        if t < best_t:
            best_bt, best_t = bt, t
    best_bt = best_bt or _heuristic_block(batch)
    _BLOCK_CACHE[(backend, bucket_cap, k)] = best_bt
    return best_bt


def model_seed(backend: str, bucket_cap: int, k: int, *,
               batch: int = 64, spec=None) -> int:
    """Seed the block cache from the analytic roofline model.

    Ranks candidate ``block_bt`` values by predicted launch cost
    (``launches·overhead + max(ops/peak, bytes/bw)`` against the
    platform's `DeviceSpec`) instead of timing them — no compiles, no
    device work.  Same cache slot empirical :func:`autotune` fills, so
    the two modes are interchangeable per site.
    """
    from repro.obs.roofline import predict_block_bt

    be = get_backend(backend)
    if not be.uses_pallas:  # lax/ref vmap the whole batch; nothing to tune
        bt = _heuristic_block(batch)
    else:
        bt = predict_block_bt(backend, bucket_cap, k, batch, spec=spec)
    _BLOCK_CACHE[(backend, bucket_cap, k)] = bt
    return bt


def clear_autotune_cache() -> None:
    """Drop every cached block size (tests / re-tuning on new hardware)."""
    _BLOCK_CACHE.clear()


# ----------------------------------------------------------- dispatch ----
def align_batch(
    texts,
    patterns,
    p_lens,
    t_lens,
    *,
    cfg: GenASMConfig = GenASMConfig(),
    backend: str | None = None,
    p_cap: int | None = None,
    emit_cigar: bool = True,
    block_bt: int | None = None,
) -> AlignResult:
    """Align a batch of (text, pattern) pairs on the selected backend.

    ``texts`` [B, t_cap] / ``patterns`` [B, p_cap] int8 buffers with
    ``t_lens`` / ``p_lens`` valid lengths (anchored semi-global, pattern
    fully consumed).  Returns a batched :class:`AlignResult` — identical
    distances/CIGARs across ``lax`` and ``pallas_dc*`` backends.
    """
    be = resolve_backend(backend)
    cap = int(patterns.shape[-1]) if p_cap is None else p_cap
    batch = int(texts.shape[0])
    if block_bt is None:
        key = (be.name, cap, cfg.k)
        mode = os.environ.get("REPRO_ALIGN_AUTOTUNE")
        if be.uses_pallas and key not in _BLOCK_CACHE:
            if mode == "model":
                model_seed(be.name, cap, cfg.k, batch=max(batch, 16))
            elif mode == "1" and not isinstance(texts, jax.core.Tracer):
                autotune(be.name, cap, cfg.k, batch=max(batch, 16), cfg=cfg)
        block_bt = block_size_for(be.name, cap, cfg.k, batch)
    return be.fn(texts, patterns, p_lens, t_lens, cfg=cfg, p_cap=cap,
                 emit_cigar=emit_cigar, block_bt=block_bt,
                 interpret=needs_interpret())
