"""95th percentile latency of every ``nn_search`` request completed in
the window, in milliseconds."""
import numpy as np


def read(ctx):
    lat = ctx.stats["latencies_s"]
    return float(np.percentile(lat, 95)) * 1e3 if lat.size else None
