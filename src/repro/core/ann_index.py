"""Asynchronously-clustered IVF index for the Knowledge Bank (§3.1, §3.2).

The paper's headline workload — neighbor discovery for graph learning —
issues ``nn_search`` against the full bank, O(N*D) per query in every
backend. This module maintains an inverted-file (IVF) approximation OFF the
serving path, exactly the knowledge-maker role CARLS defines: a background
``IVFRefresher`` thread snapshots the bank, k-means-partitions it into
``nlist`` buckets (jit-compiled Lloyd steps), and atomically swaps the new
index into the engine. Serving never blocks on clustering; queries prune to
``nprobe`` buckets via the two-stage kernel in
``repro.kernels.nn_search_ivf``, turning the hot path into
O((C + nprobe*N/C) * D).

Index layout (what makes the stage-2 kernel gather-free):

- ``centroids``   : (C, D) f32 — the coarse quantizer.
- ``packed_vecs`` : (C*cap, D) f32 — a snapshot of the bank rows grouped by
  cluster; every bucket padded to the common pow2 capacity ``cap`` so each
  bucket is a block-aligned slice the kernel can DMA directly.
- ``packed_ids``  : (C*cap,) int32 — the bank row id of each packed slot,
  -1 in padding slots.
- ``bucket_occ``  : (C,) int32 — occupied rows per bucket. ``_pack_buckets``
  fills each bucket from its start, so the occupied slots of bucket b are
  exactly the first ``bucket_occ[b]`` — the stage-2 kernels use this to
  iterate only a bucket's occupied chunks instead of the common capacity
  (the skew-proofing described in ``repro.kernels.nn_search_ivf``).

Staleness model: rows never appear or vanish (the bank is a fixed (N, D)
table), so writes after a build only leave *stale vectors* in the snapshot.
The engine counts written rows (``total_write_rows``); the index remembers
the count it was built at; their difference is the measurable staleness that
(a) triggers the refresher's rebuild and (b) gates the exact fallback.
Within the shortlist the winners are re-scored against the live table, so
staleness costs recall only — never score accuracy (see the kernel module).

Sharded banks (``ShardedIVFIndex``): the multi-device backend keeps ONE
sub-index per shard, each clustered over only the rows that shard owns, laid
out so the per-shard slice of every array IS that shard's complete local
index. Queries probe every shard's centroid table, each shard produces a
local top-k shortlist from its own buckets, and the shortlists meet in a
hierarchical merge (all-gather of (B, k) candidates + global re-top-k —
payload O(B*k*shards), constant in N, the same fan-in as the exact sharded
path). Per-shard sub-indexes rebuild independently: a hot shard goes stale
and re-clusters alone, at 1/S of the full build cost.

Trade-off knobs (docs/tuning.md): ``nlist`` (more buckets = less work per
probe, weaker partitions; per *shard* for the sharded index), ``nprobe``
(recall vs latency), ``rebuild_rows`` / ``rebuild_shard_rows`` /
``stale_rows`` (refresh rate vs clustering cost).
"""
from __future__ import annotations

import functools
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def clustered_bank(n: int, dim: int, n_centers: int, *, noise: float = 0.15,
                   seed: int = 0) -> np.ndarray:
    """Mixture-of-Gaussians bank — the workload IVF targets (embedding
    banks cluster; uniform noise is the adversarial case, not the serving
    case). Shared by the nn_search benchmark and the test suites so the
    workload definition lives in one place."""
    kc, ka, kn = jax.random.split(jax.random.key(seed), 3)
    centers = 2.0 * jax.random.normal(kc, (n_centers, dim))
    assign = jax.random.randint(ka, (n,), 0, n_centers)
    return np.asarray(centers[assign]
                      + noise * jax.random.normal(kn, (n, dim)), np.float32)


def _bucket_occupancy_stats(packed_ids, nlist: int, cap: int) -> dict:
    """Bucket-skew summary for one sub-index's packed layout. ``skew`` is
    capacity over mean occupancy — the padding-waste factor the ROADMAP's
    skewed-bank item tracks (1.0 = perfectly balanced buckets);
    ``headroom`` is how many more rows the fullest bucket can take before
    the next rebuild forces a capacity upgrade (and, sharded, a full
    repack)."""
    occ = (np.asarray(packed_ids).reshape(nlist, cap) >= 0).sum(axis=1)
    mean = float(occ.mean())
    return {
        "nlist": nlist,
        "bucket_cap": cap,
        "mean_occupancy": mean,
        "max_occupancy": int(occ.max()),
        "skew": float(cap / max(mean, 1e-9)),
        "headroom": int(cap - occ.max()),
    }


class IVFIndex:
    """Immutable clustered snapshot of a bank table (not a pytree — the
    engine passes the arrays to its jitted search fn individually)."""

    __slots__ = ("centroids", "packed_vecs", "packed_ids", "nlist",
                 "bucket_cap", "n_rows", "bucket_occ")

    def __init__(self, centroids, packed_vecs, packed_ids, *, nlist: int,
                 bucket_cap: int, n_rows: int, bucket_occ=None):
        self.centroids = centroids
        self.packed_vecs = packed_vecs
        self.packed_ids = packed_ids
        self.nlist = nlist
        self.bucket_cap = bucket_cap
        self.n_rows = n_rows
        if bucket_occ is None:          # derive from the packed layout
            bucket_occ = jnp.asarray(
                (np.asarray(packed_ids).reshape(nlist, bucket_cap) >= 0)
                .sum(axis=1).astype(np.int32))
        self.bucket_occ = bucket_occ

    def bucket_stats(self) -> dict:
        """Bucket-occupancy skew of this snapshot (see
        ``_bucket_occupancy_stats``)."""
        return _bucket_occupancy_stats(self.packed_ids, self.nlist,
                                       self.bucket_cap)


@jax.jit
def _lloyd_step(table, centroids):
    """One k-means step: L2 assignment (argmax of x.c - |c|^2/2 — the
    x-independent expansion of argmin |x-c|^2), then mean update. Empty
    clusters are reseeded with the worst-fit rows — without this, centroids
    that collapse onto one true cluster stay dead, one bucket swallows a
    large fraction of the bank, and the stage-2 shortlist (nprobe * cap)
    balloons past the brute-force cost the index exists to avoid."""
    cn = jnp.sum(centroids * centroids, axis=1)
    logits = table @ centroids.T - 0.5 * cn[None, :]
    assign = jnp.argmax(logits, axis=1)
    best = jnp.max(logits, axis=1)
    sums = jnp.zeros_like(centroids).at[assign].add(table)
    cnts = jnp.zeros((centroids.shape[0],), jnp.float32).at[assign].add(1.0)
    # badness = 0.5*|x - c|^2 for the assigned centroid; the C worst rows
    # become the reseed pool (distinct rows, far from every live centroid)
    badness = 0.5 * jnp.sum(table * table, axis=1) - best
    _, worst = jax.lax.top_k(badness, centroids.shape[0])
    new = jnp.where((cnts > 0)[:, None],
                    sums / jnp.maximum(cnts, 1.0)[:, None], table[worst])
    return new, assign


@functools.partial(jax.jit, static_argnames=("nlist",))
def _maxmin_init(table, nlist: int):
    """Greedy farthest-point seeding: every well-separated cluster gets
    exactly one seed (a random/strided init double-seeds some clusters and
    leaves others merged — 4x-skewed buckets). Deterministic."""
    sq = jnp.sum(table * table, axis=1)

    def pick(i, state):
        cents, mind = state
        c = table[jnp.argmax(mind)]
        cents = cents.at[i].set(c)
        d = sq - 2.0 * (table @ c) + jnp.sum(c * c)
        return cents, jnp.minimum(mind, d)

    c0 = table[0]
    mind = sq - 2.0 * (table @ c0) + jnp.sum(c0 * c0)
    cents = jnp.zeros((nlist, table.shape[1]), jnp.float32).at[0].set(c0)
    cents, _ = jax.lax.fori_loop(1, nlist, pick, (cents, mind))
    return cents


@jax.jit
def _centroid_shift(new, old):
    """Largest squared per-centroid movement, relative to the mean squared
    centroid norm — scale-free, so one tolerance works across banks."""
    num = jnp.max(jnp.sum((new - old) ** 2, axis=1))
    den = jnp.mean(jnp.sum(old * old, axis=1)) + 1e-12
    return num / den


def kmeans(table, nlist: int, *, iters: int = 8, tol: float = 1e-4):
    """Lloyd's algorithm, farthest-point init.
    table: (N, D) -> (centroids (C, D) f32, assign (N,) int32).

    ``iters`` is a CEILING: iteration stops early once the largest relative
    centroid movement per step drops below ``tol`` (Lloyd on clustered
    banks typically converges in 3-4 steps; the fixed-count loop was paying
    for 8). ``tol=0`` restores the fixed-iteration behavior. Determinism is
    unchanged — the stop rule depends only on the snapshot."""
    table = jnp.asarray(table, jnp.float32)
    N = table.shape[0]
    C = max(1, min(nlist, N))
    centroids = _maxmin_init(table, C)
    for _ in range(max(1, iters)):
        prev = centroids
        centroids, _ = _lloyd_step(table, prev)
        if tol and float(_centroid_shift(centroids, prev)) <= tol * tol:
            break
    # final assignment against the RETURNED centroids (the loop's assign is
    # one half-step behind — a centroid reseeded on the last step would own
    # zero rows, and stage 1 probes against these centroids)
    _, assign = _lloyd_step(table, centroids)
    return centroids, assign.astype(jnp.int32)


def _round_capacity(biggest: int) -> int:
    """Common bucket capacity >= the largest bucket (skewed data costs
    padding memory, never correctness): pow2 for tiny buckets, else the next
    multiple of 128 — the stage-2 kernel chunks buckets in 128-row tiles,
    and pow2 rounding above 128 would waste up to 2x shortlist work."""
    biggest = max(biggest, 8)
    if biggest <= 128:
        return 1 << (biggest - 1).bit_length()
    return -(-biggest // 128) * 128


def _pack_buckets(tbl, assign, C: int, cap: int, *, id_offset: int = 0):
    """Group ``tbl`` rows by their cluster ``assign`` into the block-aligned
    layout: every bucket padded to ``cap`` slots, -1 ids in the padding.
    ``id_offset`` turns local row positions into global bank ids (the
    sharded per-owner build packs slice s with offset s * n_local)."""
    N, D = tbl.shape
    order = np.argsort(assign, kind="stable")
    sa = assign[order]
    start = np.searchsorted(sa, np.arange(C))
    slots = sa * cap + (np.arange(N) - start[sa])
    packed_ids = np.full((C * cap,), -1, np.int32)
    packed_ids[slots] = (order + id_offset).astype(np.int32)
    packed_vecs = np.zeros((C * cap, D), np.float32)
    packed_vecs[slots] = tbl[order]
    return packed_vecs, packed_ids


def build_ivf_index(table, *, nlist: int = 64, iters: int = 8,
                    tol: float = 1e-4) -> IVFIndex:
    """Cluster a table snapshot and pack it into the block-aligned IVF
    layout. Runs on the caller's thread — the refresher's, in serving.
    Deterministic: the same snapshot always yields the same index
    (farthest-point init, no RNG), so rebuilds never introduce jitter."""
    tbl = np.asarray(table, np.float32)
    N, D = tbl.shape
    centroids, assign = kmeans(tbl, nlist, iters=iters, tol=tol)
    C = centroids.shape[0]
    assign = np.asarray(assign)
    occ = np.bincount(assign, minlength=C).astype(np.int32)
    cap = _round_capacity(int(occ.max()))
    packed_vecs, packed_ids = _pack_buckets(tbl, assign, C, cap)
    return IVFIndex(jnp.asarray(centroids), jnp.asarray(packed_vecs),
                    jnp.asarray(packed_ids), nlist=C, bucket_cap=cap,
                    n_rows=N, bucket_occ=jnp.asarray(occ))


class ShardedIVFIndex:
    """Per-shard sub-indexes over a row-sharded bank, one flat array set.

    Shard ``s`` owns the contiguous row range ``[s*n_local, (s+1)*n_local)``
    (the same ownership rule ``OwnerShard`` uses for every sharded op).
    Layouts are shard-major so the per-shard slice of each array is exactly
    that shard's local index — sharding them over the mesh's row axes gives
    every device its own sub-index with zero re-layout:

    - ``centroids``   : (S*C, D) f32 — shard s's coarse quantizer is rows
      ``[s*C, (s+1)*C)``.
    - ``packed_vecs`` : (S*C*cap, D) f32 — shard s's bucket tiles are rows
      ``[s*C*cap, (s+1)*C*cap)``; ``cap`` is COMMON across shards (the max
      over per-shard largest buckets) so the arrays stay rectangular.
    - ``packed_ids``  : (S*C*cap,) int32 — GLOBAL bank row ids (-1 padding),
      so merged shortlists need no offset bookkeeping.

    ``nlist`` is per shard: the bank has S*nlist buckets total. Staleness is
    tracked per shard by the engine; ``build_sharded_ivf_index`` can rebuild
    any subset of shards in place (see its docstring for the one case that
    forces a full repack)."""

    __slots__ = ("centroids", "packed_vecs", "packed_ids", "n_shards",
                 "nlist", "bucket_cap", "n_rows", "bucket_occ")

    def __init__(self, centroids, packed_vecs, packed_ids, *, n_shards: int,
                 nlist: int, bucket_cap: int, n_rows: int, bucket_occ=None):
        self.centroids = centroids
        self.packed_vecs = packed_vecs
        self.packed_ids = packed_ids
        self.n_shards = n_shards
        self.nlist = nlist              # per shard
        self.bucket_cap = bucket_cap
        self.n_rows = n_rows
        if bucket_occ is None:          # (S*C,) — global bucket order
            bucket_occ = jnp.asarray(
                (np.asarray(packed_ids).reshape(n_shards * nlist,
                                                bucket_cap) >= 0)
                .sum(axis=1).astype(np.int32))
        self.bucket_occ = bucket_occ

    def shard_stats(self) -> list:
        """Per-shard bucket-occupancy skew (capacity vs mean occupancy —
        the cross-shard load view the ROADMAP asked for). The capacity is
        COMMON across shards, so one skewed shard inflates every shard's
        padding; a shard whose ``headroom`` approaches 0 is the one whose
        next rebuild will force a full repack at a larger capacity."""
        pid = np.asarray(self.packed_ids).reshape(self.n_shards, -1)
        out = []
        for s in range(self.n_shards):
            st = _bucket_occupancy_stats(pid[s], self.nlist,
                                         self.bucket_cap)
            st["shard"] = s
            out.append(st)
        return out


def build_sharded_ivf_index(table, n_shards: int, *, nlist: int = 64,
                            iters: int = 8, tol: float = 1e-4,
                            base: Optional[ShardedIVFIndex] = None,
                            shards: Optional[Sequence[int]] = None,
                            put=jnp.asarray) -> ShardedIVFIndex:
    """Cluster a row-sharded bank snapshot into per-shard sub-indexes.

    With ``base`` and ``shards`` given, only those shards are re-clustered;
    every other shard's centroids/buckets are copied from ``base`` untouched
    — the per-shard rebuild that lets one hot shard refresh at 1/S of the
    full build cost. The one exception: if a rebuilt shard's largest bucket
    outgrows ``base.bucket_cap``, the common capacity must grow, which
    repacks (and therefore re-clusters) every shard — detectable by the
    caller as ``result.bucket_cap != base.bucket_cap``.

    Deterministic like ``build_ivf_index``: same snapshot, same shard set,
    same index. ``put`` turns each packed host array into the index's
    device array; the sharded engine passes a placement over its mesh
    rows, so the index never passes whole through one device."""
    tbl = np.asarray(table, np.float32)
    N, D = tbl.shape
    if N % n_shards:
        raise ValueError(f"bank rows {N} not divisible by {n_shards} shards")
    n_local = N // n_shards
    C = max(1, min(nlist, n_local))
    if base is not None and (base.n_shards != n_shards or base.nlist != C):
        base = None                     # shape changed: full rebuild
    if shards is not None:
        bad = [s for s in shards if not 0 <= int(s) < n_shards]
        if bad:
            raise ValueError(f"shard ids {bad} out of range "
                             f"[0, {n_shards})")
    rebuild = (range(n_shards) if base is None or shards is None
               else sorted(set(int(s) for s in shards)))
    if base is not None and not rebuild:
        return base                     # empty shard list: no-op
    built = {}                          # shard -> (centroids, assign)
    for s in rebuild:
        sl = tbl[s * n_local:(s + 1) * n_local]
        centroids, assign = kmeans(sl, C, iters=iters, tol=tol)
        built[s] = (np.asarray(centroids), np.asarray(assign))
    biggest = max(int(np.bincount(a, minlength=C).max())
                  for _, a in built.values())
    cap = _round_capacity(biggest)
    if base is not None and cap <= base.bucket_cap:
        cap = base.bucket_cap           # partial rebuild keeps the layout
    elif base is not None:
        # capacity grew: every shard must repack at the new cap
        base = None
        for s in range(n_shards):
            if s not in built:
                sl = tbl[s * n_local:(s + 1) * n_local]
                centroids, assign = kmeans(sl, C, iters=iters, tol=tol)
                built[s] = (np.asarray(centroids), np.asarray(assign))
        cap = _round_capacity(max(int(np.bincount(a, minlength=C).max())
                                  for _, a in built.values()))
    # host copies of the sub-indexes kept from base (its arrays may be
    # sharded over a mesh)
    kept = ({} if base is None else
            {name: np.asarray(getattr(base, name)) for name in
             ("centroids", "packed_vecs", "packed_ids", "bucket_occ")})
    all_cent = np.zeros((n_shards * C, D), np.float32)
    all_vecs = np.zeros((n_shards * C * cap, D), np.float32)
    all_ids = np.full((n_shards * C * cap,), -1, np.int32)
    all_occ = np.zeros((n_shards * C,), np.int32)
    for s in range(n_shards):
        lo, hi = s * C * cap, (s + 1) * C * cap
        if s in built:
            centroids, assign = built[s]
            sl = tbl[s * n_local:(s + 1) * n_local]
            pv, pi = _pack_buckets(sl, assign, C, cap,
                                   id_offset=s * n_local)
            all_cent[s * C:(s + 1) * C] = centroids
            all_vecs[lo:hi] = pv
            all_ids[lo:hi] = pi
            all_occ[s * C:(s + 1) * C] = np.bincount(assign, minlength=C)
        else:                           # keep base's sub-index verbatim
            all_cent[s * C:(s + 1) * C] = kept["centroids"][s * C:(s + 1) * C]
            all_vecs[lo:hi] = kept["packed_vecs"][lo:hi]
            all_ids[lo:hi] = kept["packed_ids"][lo:hi]
            all_occ[s * C:(s + 1) * C] = kept["bucket_occ"][s * C:(s + 1) * C]
    return ShardedIVFIndex(put(all_cent), put(all_vecs),
                           put(all_ids), n_shards=n_shards, nlist=C,
                           bucket_cap=cap, n_rows=N,
                           bucket_occ=put(all_occ))


# ---------------------------------------------------------------------------
# quantized index wrappers (int8 packed rows + per-slot scale/offset)
# ---------------------------------------------------------------------------

def _quantize_packed(packed_vecs):
    """Per-row affine int8 quantization of a packed-bucket array (numpy,
    build path — same (offset, scale) rule as
    ``repro.core.knowledge_bank.quantize_rows``). Padding slots are
    all-zero rows and quantize to (codes 0, scale 1, offset 0) — dequant 0,
    and the -1 packed id already masks them out of every shortlist."""
    vecs = np.asarray(packed_vecs, np.float32)
    hi = vecs.max(axis=-1)
    lo = vecs.min(axis=-1)
    offset = 0.5 * (hi + lo)
    scale = (hi - lo) / 254.0
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    codes = np.clip(np.round((vecs - offset[:, None]) / scale[:, None]),
                    -127, 127).astype(np.int8)
    return codes, scale, offset.astype(np.float32)


class QuantizedIVFIndex:
    """An ``IVFIndex`` whose packed rows are stored int8 + per-slot
    (scale, offset) — 4x less stage-2 snapshot memory and int8 MACs on
    the shortlist. Scoring uses the exact decomposition
    ``s (q.c) + o sum(q)`` (see ``repro.kernels.nn_search_ivf``), so the
    quantization error relative to the fp32 snapshot affects shortlist
    recall only; winners are still re-ranked live. Keeps a reference to
    the fp32 ``base`` so partial sharded rebuilds stay possible."""

    __slots__ = ("centroids", "packed_codes", "packed_scale",
                 "packed_offset", "packed_ids", "nlist", "bucket_cap",
                 "n_rows", "bucket_occ", "base")

    def __init__(self, base: IVFIndex):
        codes, scale, offset = _quantize_packed(base.packed_vecs)
        self.centroids = base.centroids          # (C, D) f32 — tiny
        self.packed_codes = jnp.asarray(codes)
        self.packed_scale = jnp.asarray(scale)
        self.packed_offset = jnp.asarray(offset)
        self.packed_ids = base.packed_ids
        self.nlist = base.nlist
        self.bucket_cap = base.bucket_cap
        self.n_rows = base.n_rows
        self.bucket_occ = base.bucket_occ
        self.base = base

    def bucket_stats(self) -> dict:
        return _bucket_occupancy_stats(self.packed_ids, self.nlist,
                                       self.bucket_cap)


class QuantizedShardedIVFIndex:
    """Per-shard sub-indexes with int8 packed rows — the sharded analogue
    of ``QuantizedIVFIndex`` (same layout rules as ``ShardedIVFIndex``;
    the live re-rank still runs against the fp32 sharded table)."""

    __slots__ = ("centroids", "packed_codes", "packed_scale",
                 "packed_offset", "packed_ids", "n_shards", "nlist",
                 "bucket_cap", "n_rows", "bucket_occ", "base")

    def __init__(self, base: ShardedIVFIndex, put=jnp.asarray):
        codes, scale, offset = _quantize_packed(base.packed_vecs)
        self.centroids = base.centroids
        self.packed_codes = put(codes)
        self.packed_scale = put(scale)
        self.packed_offset = put(offset)
        self.packed_ids = base.packed_ids
        self.n_shards = base.n_shards
        self.nlist = base.nlist
        self.bucket_cap = base.bucket_cap
        self.n_rows = base.n_rows
        self.bucket_occ = base.bucket_occ
        self.base = base

    def shard_stats(self) -> list:
        return self.base.shard_stats()


class IVFRefresher(threading.Thread):
    """Background index maker: the knowledge-maker pattern applied to the
    ANN index. Polls the engine's write counters and rebuilds the index
    whenever enough rows have been written since the last build (or no
    index exists yet). The build works on a snapshot and the swap is a
    single atomic attribute store, so serving threads never wait on it.

    On a sharded engine (``engine.ann_shards > 1``) staleness is tracked
    per shard and sub-indexes rebuild INDEPENDENTLY: each poll re-clusters
    only the shards whose written-row count since their own last build
    crossed ``rebuild_shard_rows`` (default ``rebuild_rows / n_shards``) —
    one hot shard never forces a full-bank rebuild. ``shard_rebuilds``
    counts sub-index builds; ``rebuilds`` counts swap operations.

    Thread-safety contract: the engine's ops donate the bank state, so a
    table read unguarded could be deleted under the refresher by the
    dispatcher's next op. ``KBEngine.rebuild_ann_index`` therefore copies
    the table on the device under ``engine.swap_lock``, which every
    donating dispatch also holds, and moves the copy to the host outside
    it. The write of ``engine.ann_index`` is a plain attribute store of an
    immutable value — see ``KBEngine.set_ann_index`` for the ordering
    argument that makes a torn read harmless."""

    def __init__(self, engine, *, rebuild_rows: Optional[int] = None,
                 rebuild_shard_rows: Optional[int] = None,
                 iters: int = 8, min_period_s: float = 0.01,
                 name: str = "ann-refresher"):
        super().__init__(daemon=True, name=name)
        self.engine = engine
        self.rebuild_rows = (max(1, engine.num_entries // 4)
                             if rebuild_rows is None else rebuild_rows)
        shards = getattr(engine, "ann_shards", 1)
        self.rebuild_shard_rows = (max(1, self.rebuild_rows // shards)
                                   if rebuild_shard_rows is None
                                   else rebuild_shard_rows)
        self.iters = iters
        self.min_period_s = min_period_s
        self.stop_event = threading.Event()
        self.rebuilds = 0
        self.shard_rebuilds = 0
        self.last_error: Optional[BaseException] = None

    def _stale_shards(self):
        """Shard ids past their per-shard budget (all, if no index yet)."""
        if self.engine.ann_index is None:
            return list(range(getattr(self.engine, "ann_shards", 1)))
        per_shard = np.asarray(self.engine.ann_shard_staleness_rows)
        return np.flatnonzero(per_shard >= self.rebuild_shard_rows).tolist()

    def run(self):
        while not self.stop_event.is_set():
            stale = self._stale_shards()
            if stale:
                try:
                    # the engine reports sub-indexes ACTUALLY re-clustered
                    # (a capacity overflow upgrades a partial rebuild to a
                    # full repack; len(stale) would undercount it)
                    self.shard_rebuilds += self.engine.rebuild_ann_index(
                        iters=self.iters, shards=stale)
                    self.rebuilds += 1
                    self.last_error = None
                except Exception as e:   # keep the maker alive; a dead
                    self.last_error = e  # refresher would silently freeze
                                         # the index at its last snapshot
            self.stop_event.wait(self.min_period_s)

    def stop(self, timeout_s: float = 30.0):
        self.stop_event.set()
        self.join(timeout=timeout_s)
