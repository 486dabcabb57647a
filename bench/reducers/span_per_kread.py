"""Summed duration of the named spans, in ms per 1000 reads answered."""


def reduce(ctx, spans):
    total = sum(t1 - t0 for name, t0, t1 in ctx.spans if name in spans)
    n = ctx.answered_in_window()
    if not n or not any(name in spans for name, _, _ in ctx.spans):
        return None
    return total * 1e3 * 1000.0 / n
