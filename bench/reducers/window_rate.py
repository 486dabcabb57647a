"""Bases (or reads) answered inside the window, per second of window."""
import numpy as np


def reduce(ctx, of="bases"):
    win = ctx.window
    answered = win.answered_in_window()
    work = win.lengths[answered].sum() if of == "bases" else answered.sum()
    return float(work) / win.seconds
