"""99th percentile latency of every point request completed in the
window, in milliseconds."""
import numpy as np


def read(ctx):
    lat = ctx.stats["latencies_s"]
    return float(np.percentile(lat, 99)) * 1e3 if lat.size else None
