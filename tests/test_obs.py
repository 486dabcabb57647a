"""repro.obs: spans, Perfetto export, Amdahl ledger, HTTP, roofline,
the profiler sink and the device-side names."""
import json
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from repro.obs import (NULL_TRACER, DeviceSpec, ObsServer, RooflineManager,
                       Span, TraceLog, Tracer, align_counters, build_ledger,
                       dc_window_counters, predict_block_bt, render_report)
from repro.obs.attrib import PARALLEL_STAGES, STAGE_ORDER
from repro.serve.metrics import Histogram, Metrics


# ----------------------------------------------------------------- tracer --
def test_span_nesting_parent_links():
    tr = Tracer()
    with tr.span("flush") as f:
        with tr.span("seed_filter") as a:
            pass
        with tr.span("align") as b:
            pass
    spans = {s.name: s for s in tr.log.spans()}
    assert spans["seed_filter"].parent_id == f.span_id
    assert spans["align"].parent_id == f.span_id
    assert spans["flush"].parent_id is None
    assert a.span_id != b.span_id
    # children close before (and inside) the parent window
    assert f.t_start <= a.t_start <= a.t_end <= f.t_end


def test_retroactive_add_parents_to_open_span():
    tr = Tracer()
    t0 = time.monotonic()
    with tr.span("flush") as f:
        tr.add("align", t0, t0 + 0.5, compile=True)
    s = tr.log.spans()[0]
    assert s.name == "align" and s.parent_id == f.span_id
    assert s.duration_s == pytest.approx(0.5)
    assert s.attrs == {"compile": True}


def test_nesting_is_per_thread():
    tr = Tracer()
    seen = {}

    def worker():
        # no open span on THIS thread, even while main holds one
        seen["parent"] = tr.current_parent()

    with tr.span("flush"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["parent"] is None


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("flush") as s:
        s.set(bucket=1)  # inert null span — must not raise
        tr.add("align", 0.0, 1.0)
        tr.event("submit")
    assert tr.log.spans() == []
    assert NULL_TRACER.log.spans() == []


def test_ring_buffer_drops_oldest():
    log = TraceLog(max_spans=4)
    for i in range(6):
        log.append(Span(name=f"s{i}", t_start=0.0, t_end=1.0, span_id=i))
    assert [s.name for s in log.spans()] == ["s2", "s3", "s4", "s5"]
    assert log.dropped == 2
    assert [d["name"] for d in log.last(2)] == ["s4", "s5"]


# ---------------------------------------------------------------- perfetto --
def test_chrome_export_is_valid_trace_event_json(tmp_path):
    tr = Tracer()
    t0 = time.monotonic()
    with tr.span("flush", bucket_cap=128):
        tr.add("enqueue_wait", t0 - 0.01, t0, async_=True)
        tr.add("align", t0, t0 + 0.001)
        tr.event("submit", length=100)
    path = tmp_path / "trace.json"
    tr.log.export_chrome(str(path))
    doc = json.loads(path.read_text())

    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    phases = {e["ph"] for e in events}
    assert {"M", "X", "i", "b", "e"} <= phases
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0.0 and e["ts"] >= 0.0
    # async begin/end ids pair up exactly
    b_ids = sorted(e["id"] for e in events if e["ph"] == "b")
    e_ids = sorted(e["id"] for e in events if e["ph"] == "e")
    assert b_ids == e_ids and len(b_ids) == 1
    # thread-name metadata declares every tid used by real events
    named = {e["tid"] for e in events if e["ph"] == "M"}
    used = {e["tid"] for e in events if e["ph"] != "M"}
    assert used <= named


def test_jsonl_export_round_trips(tmp_path):
    tr = Tracer()
    with tr.span("flush", batch=3):
        pass
    path = tmp_path / "trace.jsonl"
    tr.log.export_jsonl(str(path))
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rows) == 1
    assert rows[0]["name"] == "flush" and rows[0]["attrs"] == {"batch": 3}
    assert rows[0]["duration_ms"] >= 0.0


# ------------------------------------------------------------------ ledger --
def _mk(name, t0, t1, span_id, parent=None, **attrs):
    return Span(name=name, t_start=t0, t_end=t1, span_id=span_id,
                parent_id=parent, attrs=attrs)


def test_ledger_sums_to_flush_wall_time():
    # flush [0, 1.0]: seed_filter 0.6 + align 0.3 attributed, 0.1 uncovered
    spans = [
        _mk("flush", 0.0, 1.0, 1),
        _mk("seed_filter", 0.0, 0.6, 2, parent=1),
        _mk("align", 0.6, 0.9, 3, parent=1),
    ]
    led = build_ledger(spans)
    rep = led.report()
    assert rep.n_flushes == 1
    assert rep.flush_s == pytest.approx(1.0)
    total = sum(r["total_s"] for r in rep.stages
                if r["stage"] != "enqueue_wait")
    assert total == pytest.approx(rep.flush_s)  # "other" absorbs the gap
    assert led.total("other") == pytest.approx(0.1)
    assert rep.coverage == pytest.approx(0.9)
    # serial fraction = (align + other) / busy = 0.4 / 1.0
    assert rep.serial_fraction == pytest.approx(0.4)


def test_ledger_enqueue_wait_excluded_from_busy_and_coverage():
    spans = [
        _mk("flush", 0.0, 1.0, 1),
        _mk("enqueue_wait", -5.0, 0.0, 2, parent=1),
        _mk("align", 0.0, 1.0, 3, parent=1),
    ]
    rep = build_ledger(spans).report()
    assert rep.busy_s == pytest.approx(1.0)  # the 5 s wait is not busy time
    assert rep.coverage == pytest.approx(1.0)
    eq = next(r for r in rep.stages if r["stage"] == "enqueue_wait")
    assert eq["frac"] == 0.0  # a busy-fraction would be meaningless


def test_ledger_amdahl_projection():
    # one parallel stage at 50% of busy time: spd@2 = 1/(0.5 + 0.25)
    spans = [
        _mk("flush", 0.0, 1.0, 1),
        _mk("scatter", 0.0, 0.5, 2, parent=1),
        _mk("merge", 0.5, 1.0, 3, parent=1),
    ]
    rep = build_ledger(spans).report(shard_counts=(2,))
    sc = next(r for r in rep.stages if r["stage"] == "scatter")
    assert sc["parallel"]
    assert sc["speedup_x2"] == pytest.approx(4 / 3, abs=1e-3)  # rows round
    assert sc["speedup_inf"] == pytest.approx(2.0)
    assert rep.serial_fraction == pytest.approx(0.5)
    assert set(PARALLEL_STAGES) <= set(STAGE_ORDER)


def test_render_report_is_one_row_per_stage():
    spans = [_mk("flush", 0.0, 1.0, 1), _mk("align", 0.0, 1.0, 2, parent=1)]
    text = render_report(build_ledger(spans).report())
    lines = text.splitlines()
    assert "stage attribution: 1 flushes" in lines[0]
    assert any(line.startswith("align") for line in lines)
    assert any(line.startswith("other") for line in lines)


def test_ledger_unknown_stage_folds_into_other():
    led = build_ledger([_mk("flush", 0.0, 1.0, 1),
                        _mk("mystery", 0.0, 0.2, 2, parent=1)])
    assert led.total("other") == pytest.approx(1.0)  # full flush uncovered


# -------------------------------------------------------------------- http --
def _get(url: str) -> tuple[int, str]:
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, r.read().decode()


def test_obs_server_endpoints_smoke():
    metrics = Metrics()
    metrics.counter("reads_total").inc(7)
    tr = Tracer()
    with tr.span("flush"):
        with tr.span("align"):
            pass
    with ObsServer(metrics=metrics, tracer=tr, port=0) as srv:
        code, body = _get(srv.url + "/healthz")
        assert code == 200 and body == "ok\n"

        code, body = _get(srv.url + "/metrics")
        assert code == 200 and "reads_total 7" in body

        code, body = _get(srv.url + "/trace?n=1")
        doc = json.loads(body)
        assert code == 200 and len(doc["spans"]) == 1
        assert doc["spans"][0]["name"] == "flush"  # newest last

        code, body = _get(srv.url + "/attrib")
        rep = json.loads(body)
        assert code == 200 and rep["n_flushes"] == 1
        assert any(r["stage"] == "align" for r in rep["stages"])


def test_obs_server_404s():
    with ObsServer(port=0) as srv:  # nothing attached
        for path in ("/metrics", "/trace", "/attrib", "/nope"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url + path)
            assert ei.value.code == 404


# ----------------------------------------------------------------- metrics --
def test_histogram_quantiles_monotone():
    h = Histogram()
    for v in (1e-4, 5e-4, 1e-3, 2e-3, 5e-3, 1e-2, 0.1, 1.0):
        h.observe(v)
    qs = [h.quantile(q) for q in (0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0)]
    assert qs == sorted(qs)
    assert qs[-1] <= h._bounds[-1]


def test_histogram_boundary_observation_exact():
    h = Histogram(lo=1.0, hi=100.0, n_buckets=9)
    for b in h._bounds:
        h.observe(b)  # lands in the bucket it bounds, never the next one
    st = h.stats()
    assert st["count"] == len(h._bounds)
    assert st["p50"] <= st["p99"] <= h._bounds[-1]
    # clamped outlier still counts
    h.observe(1e9)
    assert h.stats()["count"] == len(h._bounds) + 1


def test_metrics_snapshot_is_flat_and_consistent():
    m = Metrics()
    m.counter("c").inc(3)
    m.gauge("g").set(2.5)
    m.histogram("h").observe(0.01)
    snap = m.snapshot()
    assert snap["c"] == 3 and snap["g"] == 2.5
    assert snap["h_count"] == 1 and snap["h_p50"] <= snap["h_p99"]
    assert "c 3" in m.render()


# ---------------------------------------------------------------- counters --
def test_counter_events_export_as_perfetto_C_and_parse(tmp_path):
    tr = Tracer()
    tr.counter("kernel/lax/cap160", word_ops=100.0, hbm_bytes=400.0)
    tr.counter("kernel/lax/cap160", word_ops=250.0, hbm_bytes=900.0)
    path = tmp_path / "trace.json"
    tr.log.export_chrome(str(path))
    doc = json.loads(path.read_text())
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 2
    for e in cs:
        assert e["name"] == "kernel/lax/cap160"
        assert set(e["args"]) == {"word_ops", "hbm_bytes"}
    # cumulative samples are monotone in both series and in time
    assert cs[0]["ts"] <= cs[1]["ts"]
    assert cs[0]["args"]["word_ops"] < cs[1]["args"]["word_ops"]
    assert cs[0]["args"]["hbm_bytes"] < cs[1]["args"]["hbm_bytes"]


def test_disabled_tracer_counter_records_nothing():
    tr = Tracer(enabled=False)
    tr.counter("kernel/x", word_ops=1.0)
    assert tr.log.spans() == []


# ---------------------------------------------------------------- roofline --
def test_dc_window_counters_hand_counted_tiny_case():
    # w=32 (one word), k=2: 32 text steps x 3 distance rows x 6 ops/cell
    c = dc_window_counters(32, 2)
    assert c["nw"] == 1
    assert c["word_ops"] == 32 * 3 * 6
    # M/I/D store: one u32 word per (step, row, vector)
    assert c["tb_bytes"] == 32 * 3 * 3 * 4
    # R-only store: one u32 word per (step incl. boundary, row)
    assert dc_window_counters(32, 2, store="r")["tb_bytes"] == 33 * 3 * 4
    with pytest.raises(ValueError):
        dc_window_counters(33, 2)  # not a word multiple
    with pytest.raises(ValueError):
        dc_window_counters(32, 2, store="nope")


def test_align_counters_hand_counted_launch_structure():
    # cap=64, w=32, o=8: commit 24 -> ceil(64/24)+2 = 5 windows;
    # batch 8 @ block 8 -> 1 grid step/window -> 5 launches
    c = align_counters("pallas_dc", 64, 2, 8, w=32, o=8, block_bt=8)
    assert c.launches == 5 and c.exact
    assert c.word_ops == 5 * 8 * (32 * 3 * 6)
    assert c.tb_bytes == 5 * 8 * (32 * 3 * 3 * 4)
    # io: per window-lane, text+pattern tiles (2w int8) + d_min (4B)
    assert c.hbm_bytes == c.tb_bytes + 5 * 8 * (2 * 32 + 4)
    # v2's R-only store is ~3x less TB traffic at equal ops
    v2 = align_counters("pallas_dc_v2", 64, 2, 8, w=32, o=8, block_bt=8)
    assert v2.word_ops == c.word_ops
    assert v2.tb_bytes < c.tb_bytes / 2.5
    # padding counts: batch 9 pads to 16 at block 8 -> 2 launches/window
    p = align_counters("pallas_dc", 64, 2, 9, w=32, o=8, block_bt=8)
    assert p.launches == 10
    assert p.word_ops == 2 * c.word_ops
    # ref is an estimate, flagged as such
    assert not align_counters("ref", 64, 2, 8).exact
    with pytest.raises(KeyError):
        align_counters("mystery_backend", 64, 2, 8)


def test_device_spec_load_and_roof():
    spec = DeviceSpec.load("tpu_v5e")
    assert spec.peak_flops == pytest.approx(197e12)
    assert spec.hbm_bw == pytest.approx(819e9)
    # roofline: bandwidth-bound below the ridge, compute-bound above
    ridge = spec.peak_word_ops / spec.hbm_bw
    assert spec.roof_ops_per_s(ridge / 10) == pytest.approx(
        ridge / 10 * spec.hbm_bw)
    assert spec.roof_ops_per_s(ridge * 10) == spec.peak_word_ops
    with pytest.raises(ValueError):
        DeviceSpec.load("no_such_device")
    for name in ("gpu_generic", "cpu_host"):
        assert DeviceSpec.load(name).peak_word_ops > 0


def test_device_spec_keyed_by_device_kind():
    """Specs follow JAX's device_kind; an unknown kind is an error."""
    assert DeviceSpec.for_device("TPU v5 lite").name == "tpu_v5e"
    assert DeviceSpec.for_device("cpu").name == "cpu_host"
    assert DeviceSpec.for_device().name == "cpu_host"  # this test host
    with pytest.raises(ValueError, match="device_kind"):
        DeviceSpec.for_device("TPU v9 imaginary")


def test_predict_block_bt_prefers_fewer_launches_under_overhead():
    # launch overhead dominates at tiny work sizes -> pick the largest
    # tile that fits the batch (one launch per window)
    slow_launch = DeviceSpec(name="x", peak_flops=1e15, peak_word_ops=1e15,
                             hbm_bw=1e15, launch_overhead_s=1.0)
    assert predict_block_bt("pallas_dc", 160, 8, 64,
                            spec=slow_launch) == 64
    # zero overhead + padding waste: batch 40 at block 64 pads 24 lanes,
    # block 8 pads none -> the model must not pick the padded tile
    no_overhead = DeviceSpec(name="y", peak_flops=1e12, peak_word_ops=1e12,
                             hbm_bw=1e12, launch_overhead_s=0.0)
    bt = predict_block_bt("pallas_dc", 160, 8, 40, spec=no_overhead)
    assert 40 % bt == 0


def test_roofline_manager_records_and_reports():
    m = Metrics()
    tr = Tracer()
    rf = RooflineManager(spec=DeviceSpec.load("cpu_host"), metrics=m,
                         tracer=tr, measure=False)
    for _ in range(3):
        rf.record_flush("lax", 160, 24, 16, align_s=0.01)
    rep = rf.report()
    assert rep["device_spec"]["name"] == "cpu_host"
    (row,) = rep["kernels"]
    assert row["kernel"] == "lax/cap160" and row["calls"] == 3
    for key in ("analytic_ops", "measured_ops", "bytes", "intensity",
                "pct_of_roof"):
        assert key in row
    assert row["analytic_ops"] > 0 and 0 < row["pct_of_roof"] < 1
    assert row["achieved_ops_per_s"] == pytest.approx(
        row["analytic_ops"] * 3 / 0.03)
    # counters land in the Metrics registry, cumulatively
    snap = m.snapshot()
    assert snap["kernel_lax_cap160_word_ops"] == pytest.approx(
        row["analytic_ops"] * 3)
    assert snap["kernel_lax_cap160_launches"] >= 0
    # ...and as monotone Perfetto counter samples
    cs = [s for s in tr.log.spans() if s.kind == "counter"]
    assert len(cs) == 3
    vals = [s.attrs["word_ops"] for s in cs]
    assert vals == sorted(vals) and vals[0] < vals[-1]


def test_roofline_manager_disabled_is_noop_and_unknown_backend_skipped():
    rf = RooflineManager(spec=DeviceSpec.load("cpu_host"), enabled=False,
                         measure=False)
    assert rf.record_flush("lax", 160, 24, 16, align_s=0.01) is None
    assert rf.report()["kernels"] == []
    rf.enabled = True
    assert rf.record_flush("graph_lax", 160, 24, 16, align_s=0.01) is None
    assert rf.report()["kernels"] == []


def test_roofline_measured_side_cost_analysis():
    rf = RooflineManager(spec=DeviceSpec.load("cpu_host"))
    rf.record_flush("lax", 64, 8, 8, align_s=0.005)
    (row,) = rf.report(measure=True)["kernels"]
    assert row["measure_error"] is None
    # XLA's CPU cost model sees only the float residue of the integer
    # DC program (DESIGN.md par. 13): demand presence and rough scale,
    # not agreement
    assert row["measured_ops"] is not None
    assert row["measured_bytes"] is not None and row["measured_bytes"] > 0
    # the analytic/measured ops ratio stays within the documented band
    assert row["analytic_ops"] / max(row["measured_ops"], 1.0) < 1024


# ------------------------------------------------------- engine integration --
def test_serve_engine_roofline_integration():
    import numpy as np

    from repro.core import minimizer_index
    from repro.serve import EngineConfig, ServeEngine

    rng = np.random.default_rng(5)
    ref = rng.integers(0, 4, size=2000).astype(np.int8)
    index = minimizer_index.build_epoched_index(ref, w=8, k=12)
    reads = [ref[i:i + 100].copy() for i in (50, 400, 900, 1300)]
    tr = Tracer()
    rf = RooflineManager(spec=DeviceSpec.load("cpu_host"), tracer=tr,
                         measure=False)
    cfg = EngineConfig(buckets=(128,), max_batch=4, minimizer_w=8,
                       minimizer_k=12)
    with ServeEngine(index, cfg, tracer=tr, roofline=rf) as eng:
        eng.map_all(reads)
        backend = eng.align_backend
    rows = rf.report(measure=False)["kernels"]
    assert rows and rows[0]["kernel"] == f"{backend}/cap128"
    assert rows[0]["calls"] >= 1 and rows[0]["align_s"] > 0
    # the align span carries the counters for per-stage attribution
    aligns = [s for s in tr.log.spans() if s.name == "align"]
    assert aligns and aligns[0].attrs["word_ops"] == rows[0]["analytic_ops"]
    rep = build_ledger(tr.log).report()
    arow = next(r for r in rep.stages if r["stage"] == "align")
    assert arow["word_ops"] == pytest.approx(
        rows[0]["analytic_ops"] * rows[0]["calls"])
    assert arow["ops_per_s"] > 0 and arow["intensity"] > 0


# ------------------------------------------------------------- http extras --
def test_trace_endpoint_bad_n_is_400_and_large_n_clamps():
    tr = Tracer(log=TraceLog(max_spans=8))
    for _ in range(12):
        with tr.span("flush"):
            pass
    with ObsServer(tracer=tr, port=0) as srv:
        for bad in ("foo", "-5", "1.5", ""):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url + f"/trace?n={bad}")
            assert ei.value.code == 400
        # n beyond the ring clamps to the ring size instead of erroring
        code, body = _get(srv.url + "/trace?n=999999999")
        assert code == 200
        assert len(json.loads(body)["spans"]) == 8


def test_roofline_endpoint_serves_kernel_rows():
    rf = RooflineManager(spec=DeviceSpec.load("cpu_host"), measure=False)
    rf.record_flush("lax", 160, 24, 16, align_s=0.02)
    rf.record_flush("lax", 320, 24, 16, align_s=0.04)
    with ObsServer(roofline=rf, port=0) as srv:
        code, body = _get(srv.url + "/roofline?measure=0")
        assert code == 200
        doc = json.loads(body)
        assert doc["device_spec"]["name"] == "cpu_host"
        kernels = {r["kernel"] for r in doc["kernels"]}
        assert kernels == {"lax/cap160", "lax/cap320"}
        for r in doc["kernels"]:
            for key in ("analytic_ops", "measured_ops", "bytes",
                        "intensity", "pct_of_roof"):
                assert key in r


def test_roofline_endpoint_404_when_unattached():
    with ObsServer(port=0) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/roofline")
        assert ei.value.code == 404


# ------------------------------------------------------- profiler sink --
def _host_events(profile_dir, names):
    """``{name: [duration_s, ...]}`` of the capture's host-plane events."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(ev.duration_ns * 1e-9)
    return out


def test_spans_and_stages_land_on_the_profiler_host_plane(tmp_path):
    import jax

    from repro.obs import StageTimer

    tr, off, timer = Tracer(), Tracer(enabled=False), StageTimer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("obs_test_flush"):
            with timer.stage("obs_test_stage"):
                time.sleep(0.02)
            with off.span("obs_test_disabled"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    got = _host_events(tmp_path, {"obs_test_flush", "obs_test_stage",
                                  "obs_test_disabled"})
    assert "obs_test_disabled" not in got  # a disabled tracer stays silent
    (flush,) = tr.log.spans()
    ((_, s0, s1, _),) = timer.times
    assert got["obs_test_flush"] == [pytest.approx(flush.duration_s,
                                                   abs=1e-3)]
    assert got["obs_test_stage"] == [pytest.approx(s1 - s0, abs=1e-3)]


def test_trace_module_needs_no_jax():
    """obs/trace.py imports, and an enabled tracer records, without JAX."""
    import os
    import subprocess
    import sys

    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro.obs.trace import Tracer, StageTimer\n"
            "tr = Tracer()\n"
            "with tr.span('flush'):\n"
            "    with StageTimer().stage('align'):\n"
            "        pass\n"
            "assert [s.name for s in tr.log.spans()] == ['flush']\n"
            "assert 'jax' not in [m for m in sys.modules if sys.modules[m]]\n")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [p for p in [env.get("PYTHONPATH")] if p])
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


# ------------------------------------------------------- device names --
@pytest.mark.parametrize("backend", ["lax", "pallas_dc"])
def test_executor_stages_carry_named_scopes(backend):
    """The compiled stages name seeding, the DC filter, the windowed DC
    step and the traceback in their op metadata."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import minimizer_index
    from repro.core.mapper import LinearMapExecutor

    rng = np.random.default_rng(3)
    ref = rng.integers(0, 4, size=2000).astype(np.int8)
    index = minimizer_index.build_reference_index(ref, w=8, k=12)
    ex = LinearMapExecutor(p_cap=96, filter_bits=64, filter_k=8,
                           minimizer_w=8, minimizer_k=12, backend=backend)
    reads = jnp.asarray(np.stack([ref[100:196], ref[700:796]]))
    lens = jnp.asarray(np.array([96, 96], np.int32))
    sf_hlo = ex._sf.lower(index, reads, lens).compile().as_text()
    sf = ex._sf(index, reads, lens)
    al_hlo = ex._align.lower(sf, lens).compile().as_text()
    # a scope is one component of an op's name path, ``vmap(...)``-wrapped
    # where it sits under a vmap
    def scoped(hlo, scope):
        return re.search(rf'op_name="[^"]*[/(]{scope}[)/]', hlo)

    for scope in ("seeding", "dc_filter"):
        assert scoped(sf_hlo, scope), scope
    for scope in ("dc_window", "traceback"):
        assert scoped(al_hlo, scope), scope


def _pallas_names(jaxpr) -> list[str]:
    """``name`` of every ``pallas_call`` in a jaxpr, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                out += _pallas_names(inner)
    return out


def test_every_pallas_kernel_has_its_own_name():
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import bitalign, genasm_dc, genasm_dc_v2, myers

    t = jnp.zeros((8, 64), jnp.int8)
    bases = jnp.zeros((8, 16), jnp.int8)
    succ = jnp.zeros((8, 16), jnp.uint32)
    pats = jnp.zeros((8, 32), jnp.int8)
    lens = jnp.full((8,), 20, jnp.int32)
    calls = {
        "genasm_dc": functools.partial(
            genasm_dc.window_dc_batch, t, t, w=64, k=4, block_bt=8),
        "genasm_dc_v2": functools.partial(
            genasm_dc_v2.window_dc_batch_v2, t, t, w=64, k=4, block_bt=8),
        "bitalign_dc": functools.partial(
            bitalign.bitalign_dc_batch, bases, succ, pats, lens, m_bits=32,
            k=4, block_bt=8),
        "bitalign_filter": functools.partial(
            bitalign.bitalign_filter_batch, bases, succ, pats, lens,
            m_bits=32, k=4, span=8, block_bt=8),
        "myers": functools.partial(
            myers.myers_distance_batch, t, pats, lens, m_bits=32,
            block_bt=8),
    }
    for name, fn in calls.items():
        jaxpr = jax.make_jaxpr(functools.partial(fn, interpret=True))()
        assert _pallas_names(jaxpr.jaxpr) == [name]
