"""Device time of Pallas kernels, in ms per 1000 reads answered.

Every Pallas custom call counts, whatever its name; the time is the
union of their intervals on each chip, averaged over the chips traced,
over the reads answered in the same profiled sample.
"""
import numpy as np

from bench.harness import devtrace


def reduce(ctx):
    p = ctx.profile
    n = ctx.answered_between(*p.mono) if p is not None else 0
    if p is None or not n or not any(d.pallas.any() for d in p.devices):
        return None
    ns = np.mean([devtrace.busy_ns(d, d.pallas) for d in p.devices])
    return float(ns) * 1e-6 * 1000.0 / n
