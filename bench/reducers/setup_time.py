"""Seconds from process start to the window's first due read."""


def reduce(ctx):
    return ctx.setup_s
