"""The whole window's share of the mesh's peak on the sharded point path:
the least time all its ``lookup`` and ``lazy_grad`` work needs on one
chip, over the cell's chip count, over the window."""
import json
import os

import floors

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(ctx):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    chips = next(c["chips"] for c in cells if c["name"] == ctx.workload)
    return floors.window_share(ctx, floors.point_floor_s(ctx) / chips)
