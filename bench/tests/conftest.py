"""CPU fixtures for the harness's own tests: nothing here touches a TPU.

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and
``bench/``) whose configurations are cut, each by its ``system``, to a
size a CPU run holds in seconds; runs through it pass ``platform="cpu"``
to skip the harness's look for a chip and drive the rest of a run
unchanged.  The copy also
holds the graph deployment's cell (``data/graph-10m.json``,
``data/graph-sr-batch.json``), added as files and manifest entries: the
benchmark leaves it out until the program maps at population variant
density, and the harness's graph path stays tested meanwhile.
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# reference length a CPU run holds in seconds, by the configuration's system
TINY = {"linear": 400_000, "graph": 200_000}
DATA = Path(__file__).resolve().parent / "data"
GRAPH_CELL = {"name": "graph-sr-batch", "config": "graph-10m",
              "traffic": "sr-batch", "chips": 1,
              "why": "the same 150 bp backlog against the variation graph"}


def copy_bench(dst: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__",
                                                  "tests"))
    return dst


def add_config(root: Path, config: dict, cell: dict, limits: dict) -> None:
    """A configuration, one cell on it and the cell's limits, added to the
    copy at ``root`` as files and manifest entries."""
    (root / f"bench/configs/{config['name']}.json").write_text(
        json.dumps(config))
    (root / f"bench/checks/{cell['name']}.json").write_text(
        json.dumps(limits))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": config["name"], "source": "test",
                           "file": f"bench/configs/{config['name']}.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append(cell)
    for m in man["end_to_end"]:
        if m["name"] == "bases_per_s":
            m["workloads"].append(cell["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def cut_to_tiny(root: Path) -> Path:
    """Every configuration of the manifest at ``root`` cut by its system."""
    man = json.loads((root / "BENCHMARK.json").read_text())
    for c in man["configs"]:
        f = root / c["file"]
        cfg = json.loads(f.read_text())
        if cfg["system"] not in TINY:
            raise ValueError(f"configuration {c['name']}: no tiny size for "
                             f"system {cfg['system']!r}")
        cfg["reference_length"] = TINY[cfg["system"]]
        f.write_text(json.dumps(cfg))
    return root


def tiny_bench(dst: Path) -> Path:
    """The benchmark with the graph cell added, not yet cut."""
    root = copy_bench(dst)
    add_config(root, json.loads((DATA / "graph-10m.json").read_text()),
               GRAPH_CELL, json.loads((DATA / "graph-sr-batch.json")
                                      .read_text()))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return cut_to_tiny(tiny_bench(tmp_path_factory.mktemp("bench")))
