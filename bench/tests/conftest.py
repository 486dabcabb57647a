"""CPU fixtures for the harness's own tests: nothing here touches a TPU.

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and
``bench/``) whose configurations are cut to a size a CPU run holds in
seconds; runs through it pass ``platform="cpu"`` to skip the harness's
look for a chip and drive the rest of a run unchanged.  The copy also
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

TINY = {"linear-chr1": 400_000, "graph-10m": 200_000}
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


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    root = copy_bench(tmp_path_factory.mktemp("bench"))
    man = json.loads((root / "BENCHMARK.json").read_text())
    shutil.copy(DATA / "graph-10m.json", root / "bench/configs/graph-10m.json")
    shutil.copy(DATA / "graph-sr-batch.json",
                root / "bench/checks/graph-sr-batch.json")
    man["configs"].append({"name": "graph-10m", "source": "SeGraM",
                           "file": "bench/configs/graph-10m.json",
                           "reduced": [], "why": "graph path"})
    man["workloads"].append(GRAPH_CELL)
    for m in man["end_to_end"]:
        if m["name"] == "bases_per_s":
            m["workloads"].append(GRAPH_CELL["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    for c in man["configs"]:
        f = root / c["file"]
        cfg = json.loads(f.read_text())
        cfg["reference_length"] = TINY[c["name"]]
        f.write_text(json.dumps(cfg))
    return root
