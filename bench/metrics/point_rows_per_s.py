"""Ids served by ``lookup`` and ``lazy_grad`` requests that completed in
the window, over the window."""


def read(ctx):
    s = ctx.stats
    return s["rows_done"] / s["window_s"]
