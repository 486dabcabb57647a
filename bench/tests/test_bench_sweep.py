"""The sweep tool sets a cell up as a run does and reads each window with
the cell's own reducers."""
import json

from bench import sweep
from bench.harness import cell


def test_sweep_prints_one_line_per_window(tiny_root, monkeypatch, capsys,
                                          tmp_path):
    real = cell.devices
    monkeypatch.setattr(cell, "devices",
                        lambda platform, chips, root: real("cpu", chips, root))
    monkeypatch.setattr(sweep, "ROOT", tiny_root)
    dump = tmp_path / "stalls.txt"
    assert sweep.main(["--workload", "linear-sr-online", "--seed", "9",
                       "--windows", "0.5@100,0.5", "--stall-dump",
                       str(dump)]) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [r["rate"] for r in rows] == [100.0, None]
    for r in rows:
        assert set(r["metrics"]) == {"latency_p50_ms", "setup_s"}
        assert r["p95_ms"] >= r["metrics"]["latency_p50_ms"] > 0
        assert r["unanswered"] == 0 and r["window_compiles"] == 0
    assert dump.exists()
