"""From the profiler's trace to device busy time, kernel time and gaps.

The traced run profiles the last ``PROFILE_S`` seconds of its window
(`Sampler`): the device records every operation of every loop
iteration, about two million events a second for these programs, and
the profiler's buffer holds a few seconds of them.  The sample is wrapped
in a ``jax.profiler.TraceAnnotation`` named ``MARKER``, entered at a
known ``time.monotonic()``; its event in the host plane gives the
sample's bounds on the profiler's clock and the offset that puts the
program's monotonic-clock spans on that clock.

On each device plane the ``XLA Ops`` line holds one event per operation
run.  Busy time is the union of those intervals inside the sample.  A
Pallas kernel is a custom call to the TPU's Mosaic compiler
(``tpu_custom_call`` in its HLO text); it is told apart from other
operations that way and not by its name, so a renamed or fused kernel
still counts.
"""
from __future__ import annotations

import glob
import re
import threading
import time
from dataclasses import dataclass, field

import numpy as np

MARKER = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
PALLAS_MARKS = ("tpu_custom_call", "mosaic")
PROFILE_S = 0.1  # seconds of device trace, at the end of the window


@dataclass
class DeviceOps:
    """One chip's operations inside the window (ns on the trace clock)."""

    name: str
    start: np.ndarray  # [k] float64
    end: np.ndarray  # [k] float64
    op: list  # [k] operation names
    pallas: np.ndarray  # [k] bool


@dataclass
class Profile:
    w0: float  # sample open, trace clock ns
    w1: float  # sample close, trace clock ns
    offset: float  # trace ns = monotonic s * 1e9 + offset
    devices: list = field(default_factory=list)

    @property
    def mono(self) -> tuple[float, float]:
        """The sample's bounds on the monotonic clock."""
        return ((self.w0 - self.offset) * 1e-9, (self.w1 - self.offset) * 1e-9)

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9


def op_name(name: str) -> str:
    """``"<opcode> <instruction>"`` from an HLO instruction's text, e.g.
    ``"while while.77"``; a short name passes through."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = re.search(r"\s([a-z][\w-]*)\(", rest)
    return f"{m.group(1) if m else '?'} {head.lstrip('%')}"


def is_pallas(name: str) -> bool:
    text = name.lower()
    return any(m in text for m in PALLAS_MARKS)


class Sampler(threading.Thread):
    """Profiles ``[t_start, t_end]`` (monotonic) from a thread of its own,
    so that starting and stopping the profiler never holds up the load."""

    def __init__(self, out_dir: str, t_start: float, t_end: float):
        super().__init__(name="bench-profiler", daemon=True)
        self.out_dir, self.t_start, self.t_end = out_dir, t_start, t_end
        self.t_open = None

    def run(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # it would record every Python call
        opts.enable_hlo_proto = False
        time.sleep(max(self.t_start - 0.5 - time.monotonic(), 0.0))
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        try:
            time.sleep(max(self.t_start - time.monotonic(), 0.0))
            with jax.profiler.TraceAnnotation(MARKER):
                self.t_open = time.monotonic()
                time.sleep(max(self.t_end - time.monotonic(), 0.0))
        finally:
            jax.profiler.stop_trace()


def load(profile_dir: str, t_open_mono: float) -> Profile | None:
    """Parse the newest ``.xplane.pb`` under ``profile_dir``; None when
    it holds no sample marker.  ``t_open_mono`` is when it was entered."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        return None
    pd = ProfileData.from_file(files[-1])
    marker = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER:
                    marker = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if marker is None:
        return None
    prof = Profile(w0=marker[0], w1=marker[1],
                   offset=marker[0] - t_open_mono * 1e9)
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        rows = []
        names: dict[str, tuple[str, bool]] = {}
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= prof.w0 or s >= prof.w1:
                    continue
                known = names.get(ev.name)
                if known is None:
                    known = names[ev.name] = (op_name(ev.name),
                                              is_pallas(ev.name))
                rows.append((max(s, prof.w0), min(e, prof.w1)) + known)
        rows.sort()
        prof.devices.append(DeviceOps(
            name=plane.name,
            start=np.array([r[0] for r in rows], np.float64),
            end=np.array([r[1] for r in rows], np.float64),
            op=[r[2] for r in rows],
            pallas=np.array([r[3] for r in rows], bool)))
    return prof


def union(start: np.ndarray, end: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merged, sorted intervals covering the same time."""
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    grp = np.cumsum(new) - 1
    ms = s[new]
    me = np.zeros(len(ms))
    np.maximum.at(me, grp, e)
    return ms, me


def busy_ns(dev: DeviceOps, mask: np.ndarray | None = None) -> float:
    s, e = (dev.start, dev.end) if mask is None else (dev.start[mask],
                                                        dev.end[mask])
    ms, me = union(s, e)
    return float(np.sum(me - ms))


def busy_s(prof: Profile) -> float:
    """Busy seconds, averaged over the chips traced."""
    if not prof.devices:
        return 0.0
    return float(np.mean([busy_ns(d) for d in prof.devices])) * 1e-9


def gaps(prof: Profile, dev: DeviceOps) -> list[tuple[float, float]]:
    """Idle intervals of one chip inside the window, trace clock ns."""
    ms, me = union(dev.start, dev.end)
    edges_s = np.concatenate([[prof.w0], me])
    edges_e = np.concatenate([ms, [prof.w1]])
    keep = edges_e > edges_s
    return list(zip(edges_s[keep].tolist(), edges_e[keep].tolist()))


def top_ops(prof: Profile, n: int = 10) -> list[list]:
    """The ``n`` operations that took most device time, per chip mean."""
    tot: dict[str, float] = {}
    for d in prof.devices:
        for name, s, e in zip(d.op, d.start, d.end):
            tot[name] = tot.get(name, 0.0) + (e - s)
    k = max(len(prof.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v * 1e-9 / k] for name, v in best]


def named_gaps(prof: Profile, spans, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of the first chip, each named by the
    innermost host span that covers its middle (``spans`` are
    ``(name, t0, t1)`` on the monotonic clock)."""
    if not prof.devices:
        return []
    g = sorted(gaps(prof, prof.devices[0]), key=lambda x: x[0] - x[1])[:n]
    sp = [(name, t0 * 1e9 + prof.offset, t1 * 1e9 + prof.offset)
          for name, t0, t1 in spans]
    out = []
    for s, e in g:
        mid = 0.5 * (s + e)
        cover = [(t1 - t0, name) for name, t0, t1 in sp if t0 <= mid <= t1]
        out.append([min(cover)[1] if cover else "no host span",
                    (e - s) * 1e-9])
    return out
