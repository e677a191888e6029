"""The whole window's share of the chip's peak on the point path: the
least time all its ``lookup`` and ``lazy_grad`` work needs, over the
window."""
import floors


def read(ctx):
    return floors.window_share(ctx, floors.point_floor_s(ctx))
