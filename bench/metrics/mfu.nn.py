"""The whole window's share of the chip's peak on nearest-neighbour
search: the least time all its ``nn_search`` work needs, over the
window."""
import floors


def read(ctx):
    return floors.window_share(ctx, floors.nn_floor_s(ctx))
