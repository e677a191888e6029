"""Least device time of the work a window did, call by call, from the
engine's call log (see ``kbserve``) and the shapes in ``work``."""
from __future__ import annotations

import numpy as np

import work


def window_share(ctx, floor_s: float):
    """A least time as a share of the window, in percent: the whole
    window's share of the chip's peak; None where no work was done."""
    return 100.0 * floor_s / ctx.stats["window_s"] if floor_s > 0 else None


def point_floor_s(ctx) -> float:
    """Sum over the window's ``lookup`` and ``lazy_grad`` engine calls."""
    if "point" not in ctx.memo:
        s = ctx.stats
        total = 0.0
        for c in s["calls"]:
            if c["op"] == "lookup":
                b = work.lookup_bytes(c["n_ids"], c["n_distinct"],
                                      c["n_pending"], s["dim"])
                total += work.least_time(0, b, ctx.peak)
            else:
                b = work.lazy_grad_bytes(c["n_ids"], c["n_distinct"],
                                         s["dim"])
                f = work.lazy_grad_flops(c["n_ids"], s["dim"])
                total += work.least_time(f, b, ctx.peak)
        ctx.memo["point"] = total
    return ctx.memo["point"]


def nn_floor_s(ctx) -> float:
    """Sum over the window's ``nn_search`` engine calls. Each query probes
    the ``nprobe`` buckets whose centroids score highest against it;
    every bucket is taken to hold rows/nlist rows (a balanced index), and
    a bucket probed by several queries of one call is read once."""
    if "nn" not in ctx.memo:
        import jax
        import jax.numpy as jnp
        s = ctx.stats
        cents = jnp.asarray(s["centroids"])
        nlist = cents.shape[0]
        per_bucket = s["rows"] / nlist
        probe = jax.jit(lambda q: jax.lax.top_k(jnp.matmul(
            q, cents.T, precision=jax.lax.Precision.HIGHEST),
            s["nprobe"])[1])
        total = 0.0
        for c in s["calls"]:
            q = np.asarray(c["payload"], np.float32)
            buckets = np.unique(np.asarray(probe(jnp.asarray(q))))
            f, b = work.ivf_work(q.shape[0],
                                 int(q.shape[0] * s["nprobe"] * per_bucket),
                                 int(buckets.size * per_bucket), nlist,
                                 s["dim"], s["k"])
            total += work.least_time(f, b, ctx.peak)
        ctx.memo["nn"] = total
    return ctx.memo["nn"]
