"""One run of one cell: set up, warm up, measure, check, report.

``set_up`` builds the deployment's data from the seed, hands it to the
service (index on the device, ``ServeEngine`` with its own defaults) and
warms every shape the window will use; ``run_cell`` then drives the
window through
``ServeEngine.submit``, and then, with the service's state freed, checks
the answers against the plain reference.  Its result is the contract's
last line as a dict; the numbers compared go to standard error too.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import NamedTuple

import numpy as np

from . import check, devtrace, drive, reference, traffic
from .manifest import BENCH_DIR, Manifest

MAX_SPANS = 1 << 21
# spawned in this order from the seed: a new stream goes last, so that
# those before it draw what they drew
STREAMS = ("data", "warmup", "window", "arrival", "sample", "extra",
           "haplotype")


class NoAccelerator(RuntimeError):
    """JAX found no chip of the kind, or fewer chips than the cell needs."""


class Data(NamedTuple):
    reference: np.ndarray  # backbone bases
    variants: reference.Variants | None


@dataclass
class Context:
    """What a metric's reducer reads."""

    window: drive.Window
    setup_s: float
    spans: list  # (name, t0, t1) on the monotonic clock, inside the window
    profile: devtrace.Profile | None

    def answered_in_window(self) -> int:
        return int(np.sum(self.window.answered_in_window()))

    def answered_between(self, t0: float, t1: float) -> int:
        """Reads answered in ``[t0, t1]`` (monotonic), e.g. the profile."""
        w = self.window
        return int(np.sum(w.answered() & (w.done >= t0) & (w.done <= t1)))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_data(config: dict, rng: np.random.Generator) -> Data:
    ref = reference.random_reference(config["reference_length"], rng)
    variants = None
    if "variant_every_bp" in config:
        variants = reference.random_variants(
            ref, every_bp=config["variant_every_bp"],
            mix=config["variant_mix"], ins_len=config["insertion_length"],
            del_span=config["deletion_span"],
            site_pitch=config["variant_site_pitch"], rng=rng)
    return Data(ref, variants)


def read_source(config: dict, data: Data,
                rng: np.random.Generator) -> traffic.Source:
    """The backbone, or with ``sample_alt_share`` a sample's haplotype that
    takes each variant's alt allele with that probability."""
    if "sample_alt_share" not in config:
        return traffic.Source(data.reference)
    hap = reference.haplotype(data.reference, data.variants,
                              config["sample_alt_share"], rng)
    return traffic.Source(hap.seq, hap.first_backbone)


def streams(seed: int) -> dict:
    """One generator per name in ``STREAMS``, spawned from ``seed``."""
    return dict(zip(STREAMS, (np.random.default_rng(s) for s in
                              np.random.SeedSequence(seed).spawn(
                                  len(STREAMS)))))


def compile_cache(root: Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / BENCH_DIR / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices(platform: str, chips: int, root: Path):
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoAccelerator(f"JAX runs on {devs[0].platform}, not {platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX has "
                            f"{len(devs)}")
    if platform == "tpu":
        with open(root / BENCH_DIR / "harness" / "peaks.json") as f:
            peaks = json.load(f)["devices"]
        if devs[0].device_kind not in peaks:
            raise NoAccelerator(f"no peaks known for {devs[0].device_kind}")
    return devs[:chips]


def peak_memory(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def reduce_metrics(manifest: Manifest, metrics: list[dict],
                   ctx: Context) -> dict:
    out = {}
    for m in metrics:
        spec = manifest.metric_spec(m["name"])
        mod = importlib.import_module(f"bench.reducers.{spec['reducer']}")
        v = mod.reduce(ctx, **spec.get("params", {}))
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


@dataclass
class Service:
    """A cell's service, set up from the seed and warmed up."""

    man: Manifest
    cell: dict
    mix: dict
    spec: dict  # the cell's correctness limits and control
    system: ModuleType
    data: Data
    devs: list
    engine: object  # ServeEngine
    tracer: object | None
    source: traffic.Source
    plan: traffic.Plan
    warm_counts: dict  # the engine's trace counts after the warm-up
    streams: dict


def set_up(root: Path, workload: str, seed: int, seconds: float,
           trace: bool, *, platform: str = "tpu",
           control: bool = False) -> Service:
    """Data, index and engine from the seed; every shape of the cell's
    traffic warmed up, so that nothing compiles in a window."""
    man = Manifest.load(root)
    cell = man.workload(workload)
    config = man.config(cell["config"])
    mix = man.traffic(cell["traffic"])
    spec = man.limits(workload)
    rngs = streams(seed)

    compile_cache(root)
    devs = devices(platform, cell["chips"], root)
    from repro.obs.trace import TraceLog, Tracer
    from repro.serve import ServeEngine

    system = importlib.import_module(f"bench.systems.{config['system']}")
    data = make_data(config, rngs["data"])
    overrides = dict(config.get("engine", {}))
    if control:
        overrides.update(spec["control"])
        log(f"control: the engine runs with {spec['control']}")
    index, ecfg = system.build(config, data, overrides)
    source = read_source(config, data, rngs["haplotype"])
    plan = traffic.plan(mix, source, seconds=seconds,
                        max_batch=ecfg.max_batch, rng_warm=rngs["warmup"],
                        rng_window=rngs["window"],
                        rng_arrival=rngs["arrival"])
    tracer = Tracer(log=TraceLog(MAX_SPANS)) if trace else None
    engine = ServeEngine(index, ecfg, tracer=tracer)

    def make_read(cap: int) -> np.ndarray:
        lo = max([c for c in ecfg.buckets if c < cap], default=0)
        n = max(lo + 1, cap - 12)
        return traffic.simulate_reads(source.seq, np.array([n]),
                                      mix["error_profile"],
                                      rngs["extra"]).read(0)

    caps = {ecfg.bucket_for(int(n)) for n in
            np.unique(np.concatenate([plan.window.lengths,
                                      plan.warmup.lengths]))}
    before = drive.warm_up(engine, plan.warmup, caps, make_read)
    return Service(man, cell, mix, spec, system, data, devs, engine, tracer,
                   source, plan, before, rngs)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_process: float, platform: str = "tpu",
             control: bool = False, fault=None,
             settle_s: float = drive.SETTLE_S) -> dict:
    """One run; returns the result line.  ``fault(engine)``, when given,
    breaks the service under the window, and ``settle_s`` shortens the
    wait for answers past the close (the harness's own tests)."""
    svc = set_up(root, workload, seed, seconds, trace, platform=platform,
                 control=control)
    man, data, system, devs = svc.man, svc.data, svc.system, svc.devs
    engine, tracer, plan = svc.engine, svc.tracer, svc.plan
    metrics = man.metrics_for(workload, trace)
    if fault is not None:
        fault(engine)
    if tracer is not None:
        tracer.log.clear()
    gc.collect()
    gc.freeze()  # set-up's objects stay out of the window's collections
    sampler = None
    setup_s = time.monotonic() - t_process
    if trace:
        now = time.monotonic()
        sampler = devtrace.Sampler(
            tempfile.mkdtemp(prefix="bench-profile-"),
            now + max(seconds - devtrace.PROFILE_S, 0.0), now + seconds)
        sampler.start()
    win = window(engine, plan, seconds)
    if sampler is not None:
        sampler.join()
        log(f"trace: profiler stopped {time.monotonic() - win.t_close:.1f} s "
            f"after the close")
    drive.settle(win, settle_s)
    compiles = (sum(engine.trace_counts.values())
                - sum(svc.warm_counts.values()))
    mem = peak_memory(devs)
    engine.close()
    spans = []
    if tracer is not None:
        if tracer.log.dropped:
            log(f"warning: the trace log dropped {tracer.log.dropped} spans")
        spans = [(s.name, s.t_start, s.t_end) for s in tracer.log.spans()
                 if s.kind in ("span", "async")
                 and s.t_start >= win.t_open and s.t_end <= win.t_close]
    spec, rng_sample = svc.spec, svc.streams["sample"]
    del engine, svc  # the service's device state goes before the reference
    gc.collect()

    profile = None
    if sampler is not None:
        t0 = time.monotonic()
        if sampler.t_open is not None:
            profile = devtrace.load(sampler.out_dir, sampler.t_open)
        shutil.rmtree(sampler.out_dir, ignore_errors=True)
        log(f"trace: read in {time.monotonic() - t0:.1f} s")
    ctx = Context(win, setup_s, spans, profile)
    values = reduce_metrics(man, metrics, ctx)

    t0 = time.monotonic()
    checks, info = check.compare(win, plan.window, data, system.answer,
                                 spec, compiles, rng_sample)
    log(f"reference: compared in {time.monotonic() - t0:.1f} s")
    lag = generator_lag(win)
    log(f"{workload}: {len(win.results)} reads offered, "
        f"{ctx.answered_in_window()} answered in the {win.seconds:.3f} s "
        f"window; generator lag p99 {lag * 1e3:.3f} ms; setup "
        f"{setup_s:.3f} s; device peak {mem} B")
    for line in check.report_lines(checks, info):
        log(line)
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": check.is_correct(checks),
              "attempted": len(win.results),
              "failed": int(checks["unanswered"][0]),
              "metrics": values, "device": device}
    if trace:
        if profile is not None and profile.devices:
            device["busy_s"] = devtrace.busy_s(profile)
            device["window_s"] = profile.window_s
            result["breakdown"] = {
                "device_ops": devtrace.top_ops(profile),
                "idle_gaps": devtrace.named_gaps(
                    profile, [s for s in spans if s[0] != "enqueue_wait"])}
        else:
            log("warning: the profiler's trace holds no device operations")
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def window(engine, plan: traffic.Plan, seconds: float) -> drive.Window:
    if plan.due is None:
        return drive.backlog(engine, plan.window, seconds, plan.outstanding)
    return drive.poisson(engine, plan.window, plan.due)


def generator_lag(win: drive.Window) -> float:
    lag = win.submitted - win.due
    lag = lag[np.isfinite(lag)]
    return float(np.quantile(lag, 0.99)) if len(lag) else 0.0
