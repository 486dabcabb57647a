"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the chips traced."""
from bench.harness import devtrace


def reduce(ctx):
    p = ctx.profile
    if p is None or not p.devices or p.window_s <= 0:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(p) / p.window_s)
