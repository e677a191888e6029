"""Operations and bytes that a piece of work needs, from its shapes alone,
and the chip's peaks they are held against.

Each count is of what the operation requires, whatever implements it, so
that a later kernel can neither silence a roofline share nor push it past
100%: a kernel that streams the whole bank still gets credit only for the
rows the request needs.
"""
from __future__ import annotations

import json
import os

F32 = 4
I32 = 4

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str, path: str = _PEAKS) -> dict:
    """Published peaks of one chip of ``device_kind``. A kind that is not
    in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["kinds"]:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{path}; known: {sorted(table['kinds'])}")
    return table["kinds"][device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at best: the larger of the compute and the
    memory bound."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def lookup_bytes(n_ids: int, n_distinct: int, n_pending: int,
                 dim: int) -> int:
    """A lookup of ``n_ids`` ids over ``n_distinct`` rows, ``n_pending`` of
    which hold cached gradients: read each distinct row and its gradient
    count; for each pending row read its gradient sum and squared norm,
    write the row, clear the cache (sum, count, squared norm) and bump its
    version; write one output row per id."""
    read = n_distinct * (dim * F32 + F32)
    pending = n_pending * (dim * F32 + F32          # read sum, sqnorm
                           + dim * F32              # write row
                           + dim * F32 + 2 * F32    # clear the cache
                           + 2 * I32)               # version read + write
    out = n_ids * (dim * F32 + I32)                 # output row, id in
    return read + pending + out


def lazy_grad_bytes(n_ids: int, n_distinct: int, dim: int) -> int:
    """Caching ``n_ids`` gradients over ``n_distinct`` rows: read the ids
    and gradients; read and write each distinct row's gradient sum, count,
    squared norm and norm EMA."""
    per_row = dim * F32 + 3 * F32
    return n_ids * (dim * F32 + I32) + 2 * n_distinct * per_row


def lazy_grad_flops(n_ids: int, dim: int) -> int:
    """Squared norm, clip scale and accumulate per gradient."""
    return n_ids * 4 * dim


def ivf_work(n_queries: int, scored_rows: int, distinct_rows: int,
             nlist: int, dim: int, k: int) -> tuple:
    """Two-stage IVF search: every query scores all ``nlist`` centroids
    and the ``scored_rows`` (summed over queries) rows of its probed
    buckets. Bytes: the centroids once, each distinct probed row once,
    the queries in and (score, id) pairs out."""
    flops = 2 * dim * (n_queries * nlist + scored_rows)
    nbytes = (nlist * dim * F32 + distinct_rows * (dim * F32 + I32)
              + n_queries * dim * F32 + n_queries * k * 2 * F32)
    return flops, nbytes

