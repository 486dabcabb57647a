"""Linear deployment: a minimizer index of one reference, on the device.

The program-facing side of a ``"system": "linear"`` configuration: it
hands the seeded reference to the service's own index builder and
engine, and reads the service's answers back.  Nothing here judges them.
"""
from __future__ import annotations

import numpy as np


def build(config: dict, data, overrides: dict):
    """``(index, EngineConfig)`` for the service, built on the device."""
    from repro.core.minimizer_index import build_epoched_index
    from repro.serve import EngineConfig

    index = build_epoched_index(data.reference, w=config["minimizer_w"],
                                k=config["minimizer_k"])
    return index, EngineConfig(workload="linear", **overrides)


def answer(result) -> tuple[int, int, np.ndarray, None]:
    """``(position, distance, ops, path)`` of one ``ServeResult``."""
    return (int(result.position), int(result.distance),
            np.asarray(result.ops[:result.n_ops]), None)
