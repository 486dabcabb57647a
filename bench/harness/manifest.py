"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration: the ``file`` its entry gives (``bench/configs/``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a metric: ``bench/metrics/<name>.json``, naming its reducer
  (``bench/reducers/<reducer>.py``) and the reducer's parameters;
* a cell's correctness limits: ``bench/checks/<workload>.json``.

A cell, configuration, mix or metric is added with new files and a
manifest entry; no file of the harness changes.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH_DIR = "bench"


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def check_name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not a plain name")
    return name


@dataclass
class Manifest:
    """The parsed manifest; every lookup is by name."""

    root: Path
    data: dict

    @classmethod
    def load(cls, root: Path) -> "Manifest":
        root = Path(root)
        m = cls(root, _load(root / "BENCHMARK.json"))
        m.validate()
        return m

    # ---------------------------------------------------------- lookups --
    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return _load(self.root / c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load(self.root / BENCH_DIR / "traffic" / f"{name}.json")

    def metric_spec(self, name: str) -> dict:
        return _load(self.root / BENCH_DIR / "metrics" / f"{name}.json")

    def limits(self, workload: str) -> dict:
        return _load(self.root / BENCH_DIR / "checks" / f"{workload}.json")

    def metrics_for(self, workload: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer
        ones: those that list it, or list no cells at all."""
        group = self.data["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]

    # ------------------------------------------------------- validation --
    def validate(self) -> None:
        d = self.data
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            seen = set()
            for e in d[key]:
                n = check_name(key, e["name"])
                if n in seen:
                    raise ValueError(f"{key}: {n!r} appears twice")
                seen.add(n)
        metric_names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
        if len(set(metric_names)) != len(metric_names):
            raise ValueError("two metrics share a name")
        configs = {c["name"] for c in d["configs"]}
        cells = {w["name"] for w in d["workloads"]}
        for w in d["workloads"]:
            check_name("traffic", w["traffic"])
            if w["config"] not in configs:
                raise ValueError(f"{w['name']}: unknown config {w['config']}")
        for c in d["configs"]:
            for k in c["reduced"]:
                check_name("reduced key", k)
        e2e = {m["name"] for m in d["end_to_end"]}
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT.match(m["unit"]):
                raise ValueError(f"{m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise ValueError(f"{m['name']}: better {m['better']!r}")
            unknown = set(m.get("workloads", [])) - cells
            if unknown:
                raise ValueError(f"{m['name']}: unknown cells {unknown}")
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                raise ValueError(f"{m['name']}: moves unknown {m['moves']}")
