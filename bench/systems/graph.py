"""Graph deployment: a tiled variation-graph index, on the device.

The program-facing side of a ``"system": "graph"`` configuration: the
seeded backbone and variant list go to the service's own graph index
builder, with tiles wide enough for the engine's largest bucket rung.
"""
from __future__ import annotations

import numpy as np

from bench.harness.reference import KINDS


def build(config: dict, data, overrides: dict):
    """``(index, EngineConfig)`` for the service, built on the device."""
    from repro.core.segram.graph import Variant
    from repro.graph.index import build_epoched_graph_index
    from repro.serve import EngineConfig

    cfg = EngineConfig(workload="graph", **overrides)
    v = data.variants
    variants = [
        Variant(int(p), KINDS[k], tuple(int(b) for b in alt[:n]), int(s))
        for p, k, alt, n, s in zip(v.pos, v.kind, v.alt, v.alt_len, v.span)]
    index = build_epoched_graph_index(
        data.reference, variants, w=config["minimizer_w"],
        k=config["minimizer_k"],
        window=max(cfg.buckets) + 2 * cfg.genasm.w)
    return index, cfg


def answer(result) -> tuple[int, int, np.ndarray, np.ndarray]:
    """``(position, distance, ops, path)`` of one ``ServeResult``."""
    n = result.n_ops
    return (int(result.position), int(result.distance),
            np.asarray(result.ops[:n]), np.asarray(result.path[:n]))
