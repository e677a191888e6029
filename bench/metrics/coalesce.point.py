"""Requests per device dispatch in the window, from the server's own
counters (``KnowledgeBankServer.metrics``)."""


def read(ctx):
    c = ctx.stats["counters"]
    return c["requests"] / c["dispatches"] if c["dispatches"] else None
