"""Share of the sharded point path's roofline: the least time the
window's ``lookup`` and ``lazy_grad`` engine calls need on one chip
(``floors``) over the cell's chip count, since each distinct row is read
and written by its owner chip only, over the device busy time inside
``engine.*`` spans averaged over the chips."""
import json
import os

import floors

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.span_device_s("engine.")
    if busy <= 0:
        return None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    chips = next(c["chips"] for c in cells if c["name"] == ctx.workload)
    return 100.0 * floors.point_floor_s(ctx) / chips / busy
