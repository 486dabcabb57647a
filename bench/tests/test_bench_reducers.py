"""Span and device-trace reducers on recorded inputs."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import devtrace
from bench.reducers import (device_idle_share, device_pallas_per_kread,
                            span_per_kread, span_quantile)

DATA = Path(__file__).parent / "data"


def ctx(spans=(), profile=None, answered=1000):
    return SimpleNamespace(spans=list(spans), profile=profile,
                           answered_in_window=lambda: answered,
                           answered_between=lambda t0, t1: answered)


SPANS = ([("seed_filter", 0.0 + i, 0.002 + i) for i in range(5)]
         + [("align", 0.002 + i, 0.010 + i) for i in range(5)]
         + [("enqueue_wait", 0.0, 0.001 * k) for k in range(1, 101)])


def test_span_reducers():
    c = ctx(SPANS, answered=160)
    # 5 spans of 2 ms over 160 reads: 62.5 ms per 1000 reads
    assert span_per_kread.reduce(c, spans=["seed_filter"]) == pytest.approx(
        62.5)
    assert span_per_kread.reduce(c, spans=["seed_filter", "align"]) == \
        pytest.approx(62.5 + 250.0)
    assert span_per_kread.reduce(c, spans=["dc_filter"]) is None
    assert span_quantile.reduce(c, spans=["enqueue_wait"], q=0.95) == \
        pytest.approx(95.05)
    assert span_quantile.reduce(c, spans=["nothing"], q=0.5) is None


def synthetic_profile():
    dev = devtrace.DeviceOps(
        name="/device:TPU:0",
        start=np.array([0.0, 50.0, 100.0, 400.0, 420.0]),
        end=np.array([200.0, 150.0, 300.0, 450.0, 430.0]),
        op=["fusion", "copy", "kernel", "fusion", "kernel"],
        pallas=np.array([False, False, True, False, True]))
    return devtrace.Profile(w0=0.0, w1=1000.0, offset=0.0, devices=[dev])


def test_busy_union_idle_share_and_pallas_time():
    p = synthetic_profile()
    dev = p.devices[0]
    assert devtrace.busy_ns(dev) == 350.0  # [0,300] and [400,450]
    assert devtrace.busy_ns(dev, dev.pallas) == 210.0
    assert device_idle_share.reduce(ctx(profile=p)) == pytest.approx(65.0)
    # 210 ns over 1000 reads: 2.1e-4 ms per 1000 reads
    assert device_pallas_per_kread.reduce(ctx(profile=p)) == \
        pytest.approx(2.1e-4)
    gaps = devtrace.gaps(p, dev)
    assert gaps == [(300.0, 400.0), (450.0, 1000.0)]
    named = devtrace.named_gaps(p, [("emit", 3e-7, 4e-7)], n=2)
    assert named[0] == ["no host span", pytest.approx(550e-9)]
    assert named[1] == ["emit", pytest.approx(100e-9)]
    assert devtrace.top_ops(p, 1)[0][0] == "fusion"


def test_no_trace_reads_nothing():
    assert device_idle_share.reduce(ctx()) is None
    p = synthetic_profile()
    p.devices[0].pallas[:] = False
    assert device_pallas_per_kread.reduce(ctx(profile=p)) is None


def sweep_busy(start, end):
    """Busy time by a plain walk over the sorted intervals."""
    busy, cur_s, cur_e = 0.0, None, None
    for a, b in sorted(zip(start, end)):
        if cur_e is None or a > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = a, b
        else:
            cur_e = max(cur_e, b)
    return busy + (cur_e - cur_s if cur_e is not None else 0.0)


def test_recorded_tpu_trace():
    """6 ms of a v5e trace of the graph batch cell (one BitAlign call)."""
    rec = json.loads((DATA / "v5e_graph_ops.json").read_text())
    for hlo in rec["pallas_hlo"]:
        assert devtrace.is_pallas(hlo)
        assert devtrace.op_name(hlo).startswith("custom-call ")
    start = np.array(rec["start"], float)
    end = start + np.array(rec["dur"], float)
    op = [rec["names"][i] for i in rec["op"]]
    pallas = np.array([o in rec["pallas_op"] for o in op])
    dev = devtrace.DeviceOps(name=rec["plane"], start=start, end=end, op=op,
                             pallas=pallas)
    p = devtrace.Profile(w0=0.0, w1=float(end.max()) + 1e5, offset=0.0,
                         devices=[dev])
    assert devtrace.busy_ns(dev) == pytest.approx(sweep_busy(start, end))
    assert devtrace.busy_ns(dev, pallas) == pytest.approx(
        sweep_busy(start[pallas], end[pallas]))
    assert 0 < devtrace.busy_s(p) < p.window_s
    assert device_idle_share.reduce(ctx(profile=p)) == pytest.approx(
        100 * (1 - sweep_busy(start, end) / (p.w1 - p.w0)))
    assert device_pallas_per_kread.reduce(ctx(profile=p, answered=32)) > 0
    gaps = devtrace.gaps(p, dev)
    assert sum(e - s for s, e in gaps) == pytest.approx(
        (p.w1 - p.w0) - sweep_busy(start, end))


def test_marker_found_in_a_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(1024)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(devtrace.MARKER):
            t_open = time.monotonic()
            for _ in range(3):
                f(x).block_until_ready()
            time.sleep(0.05)
            t_close = time.monotonic()
    finally:
        jax.profiler.stop_trace()
    p = devtrace.load(str(tmp_path), t_open)
    assert p is not None and p.devices == []  # the CPU has no device plane
    assert p.window_s == pytest.approx(t_close - t_open, abs=2e-3)
    assert p.offset + t_open * 1e9 == pytest.approx(p.w0)
