"""Quantile of every due read's latency, from its due time, in ms.

A read with no answer counts at the longest wait the run allows, so a
lost read raises the tail instead of leaving it.
"""
import numpy as np

from bench.harness.drive import SETTLE_S


def reduce(ctx, q):
    win = ctx.window
    if len(win.due) == 0:
        return None
    done = np.where(win.answered(), win.done, win.t_close + SETTLE_S)
    return float(np.quantile(done - win.due, q)) * 1e3
