"""The manifest finds every file by name, and new cells and configurations
need no harness edit."""
import importlib
import json

import pytest

from bench.harness import cell
from bench.harness.manifest import NAME, UNIT, Manifest

from .conftest import (DATA, ROOT, add_config, copy_bench, cut_to_tiny,
                       tiny_bench)


def test_every_named_file_loads():
    man = Manifest.load(ROOT)
    d = man.data
    for c in d["configs"]:
        cfg = man.config(c["name"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
        importlib.import_module(f"bench.systems.{cfg['system']}")
    for w in d["workloads"]:
        man.traffic(w["traffic"])
        limits = man.limits(w["name"])["limits"]
        assert {"unanswered", "bad_alignments"} <= set(limits)
        for trace in (False, True):
            assert man.metrics_for(w["name"], trace)
    for m in d["end_to_end"] + d["per_layer"]:
        spec = man.metric_spec(m["name"])
        mod = importlib.import_module(f"bench.reducers.{spec['reducer']}")
        assert callable(mod.reduce)


def test_names_and_units_are_plain():
    d = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in d[k]]
    names += [w["traffic"] for w in d["workloads"]]
    names += [k for c in d["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in d["end_to_end"] + d["per_layer"])
    for bad in ("two words", "a,b", "a/b", "-lead", "x" * 65, "µs"):
        assert not NAME.match(bad)


def test_a_cell_is_added_by_files_and_a_manifest_entry(tmp_path, tiny_root):
    root = copy_bench(tmp_path)
    for c in json.loads((tiny_root / "BENCHMARK.json").read_text())["configs"]:
        (root / c["file"]).write_text((tiny_root / c["file"]).read_text())
    bench = root / "bench"
    mix = json.loads((bench / "traffic" / "sr-online.json").read_text())
    mix.update(rate_reads_per_s=200, reads=[{"length": 100, "share": 1.0}])
    (bench / "traffic" / "sr-100.json").write_text(json.dumps(mix))
    cfg = json.loads((bench / "configs" / "linear-chr1.json").read_text())
    cfg["name"] = "linear-small"
    (bench / "configs" / "linear-small.json").write_text(json.dumps(cfg))
    (bench / "metrics" / "reads_per_s.json").write_text(
        json.dumps({"reducer": "window_rate", "params": {"of": "reads"}}))
    (bench / "checks" / "small-100.json").write_text(
        (bench / "checks" / "linear-sr-online.json").read_text())
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "linear-small", "source": "x",
                           "file": "bench/configs/linear-small.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "small-100", "config": "linear-small",
                             "traffic": "sr-100", "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "reads_per_s", "unit": "reads/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["small-100"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    r = cell.run_cell(root, "small-100", 77, 0.5, False, t_process=0.0,
                      platform="cpu")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"reads_per_s", "setup_s"}
    assert r["metrics"]["reads_per_s"]["value"] > 0


def test_unknown_names_are_refused(tmp_path):
    root = copy_bench(tmp_path)
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"][0]["traffic"] = "no such"
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    with pytest.raises(ValueError):
        Manifest.load(root)


@pytest.mark.parametrize("system", ["linear", "graph"])
def test_a_configuration_is_added_by_files_alone(tmp_path, system):
    src, limits = {
        "linear": (ROOT / "bench/configs/linear-chr1.json",
                   ROOT / "bench/checks/linear-sr-batch.json"),
        "graph": (DATA / "graph-10m.json", DATA / "graph-sr-batch.json"),
    }[system]
    config = json.loads(src.read_text())
    config["name"] = f"{system}-never-seen"
    new = {"name": f"{system}-never-seen-sr-batch", "config": config["name"],
           "traffic": "sr-batch", "chips": 1, "why": "test"}
    root = tiny_bench(tmp_path)
    add_config(root, config, new, json.loads(limits.read_text()))
    cut_to_tiny(root)
    r = cell.run_cell(root, new["name"], 2 ** 31 + 29, 0.5, False,
                      t_process=0.0, platform="cpu", settle_s=10.0)
    assert r["correct"], r["checks"]
    assert r["metrics"]["bases_per_s"]["value"] > 0


def test_a_configuration_of_an_unknown_system_is_named(tmp_path):
    config = json.loads((DATA / "graph-10m.json").read_text())
    config.update(name="kmer-count", system="kmer")
    root = tiny_bench(tmp_path)
    add_config(root, config, {"name": "kmer-sr-batch", "config": "kmer-count",
                              "traffic": "sr-batch", "chips": 1,
                              "why": "test"},
               json.loads((DATA / "graph-sr-batch.json").read_text()))
    with pytest.raises(ValueError, match="kmer-count.*'kmer'"):
        cut_to_tiny(root)
