"""Quantile of the durations of the named spans in the window, in ms."""
import numpy as np


def reduce(ctx, spans, q):
    d = [t1 - t0 for name, t0, t1 in ctx.spans if name in spans]
    return float(np.quantile(d, q)) * 1e3 if d else None
