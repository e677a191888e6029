"""Device time of the lookups' all-reduce (the ``psum`` of the rows) in
the traced window, in milliseconds per ``lookup`` engine call: on each
chip the union of its ``all-reduce`` ops, an async ``all-reduce-start`` /
``all-reduce-done`` pair taken as one interval from the start's start to
the done's end, averaged over the chips."""
import re

import kbtrace

_OPCODE = re.compile(r" (all-reduce(?:-start|-done)?)\(")


def intervals(ops) -> list:
    """(start, end) of each all-reduce on one chip; the n-th ``-done``
    closes the n-th ``-start``."""
    out, open_ = [], []
    for s, e, name in sorted(ops):
        m = _OPCODE.search(name)
        if m is None:
            continue
        if m.group(1) == "all-reduce-start":
            open_.append(s)
        elif m.group(1) == "all-reduce-done":
            if open_:
                out.append((open_.pop(0), e))
        else:
            out.append((s, e))
    return out


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    calls = sum(1 for c in ctx.stats["calls"] if c["op"] == "lookup")
    if not calls:
        return None
    per_chip = [kbtrace.tr.overlap(kbtrace.tr.union(intervals(ops)),
                                   tr.t0, tr.t1)
                for ops in tr.devices.values()]
    return 1e3 * sum(per_chip) / len(per_chip) / calls
