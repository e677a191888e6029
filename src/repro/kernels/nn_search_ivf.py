"""Two-stage IVF nn_search: centroid probing + bucket-only Pallas top-k.

The exact blocked kernel (``repro.kernels.nn_search``) streams the whole
bank HBM->VMEM per query batch — O(N*D) per call no matter what the queries
are. This module is the approximate serving path built on the inverted-file
index from ``repro.core.ann_index``:

- stage 1 scores the queries against the ``C`` k-means centroids and keeps
  the ``nprobe`` best partitions per query — O(C*D);
- stage 2 scores each query only against the rows of its probed buckets —
  O(nprobe * cap * D) — and keeps a running top-k.

The bank rows live in the index as ``packed_vecs``: a (C*cap, D) copy
grouped by cluster, each bucket padded with ``-1`` ids to the common pow2
capacity ``cap``. That layout makes every per-query shortlist a set of
*block-aligned slices*, so the stage-2 kernel needs no hardware gather: a
scalar-prefetched (B, n_chunks) block-selector table drives the BlockSpec
index_map, and the TPU DMAs exactly the shortlisted (LB, D) bucket tiles
HBM->VMEM — nothing else. Per chunk the kernel runs the same running-top-k
merge as the exact kernel (``_merge_topk``, reused) with the packed ids
standing in for the iota.

Because a row lives in exactly one bucket and probes are per-query, the
result is a pure function of (index, table, query) — coalescing a batch of
IVF searches into one call is deterministic, same as the exact path.

Skew-proofing: buckets are padded to the COMMON capacity ``cap``, so on a
skewed bank most chunks of most buckets are pure padding — work the
max-bucket layout forces on every probe. ``ivf_chunk_plan`` fixes this
through the same scalar-prefetch table: given the per-bucket occupancy
(``bucket_occ``, carried by the index since the packer fills each bucket
front-to-back), it compacts each query's OCCUPIED chunks to the front of
its selector row, repeats the last valid chunk index over the tail (a
repeated block index is not re-fetched — the pipeline skips the DMA), and
hands the kernel a per-query valid count; the merge body is skipped with
``pl.when`` past it. Results are bit-identical to the dense plan — skipped
chunks contain only NEG-masked padding that can never enter the top-k —
but FLOPs (and on device, DMAs) scale with occupancy instead of capacity.

Final step: the k winners are re-scored against the LIVE table (a (B*k)-row
gather, negligible) so returned scores are exact for the rows found even
when the index snapshot has gone stale — stale assignments only cost
recall, never score accuracy.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.env import resolve_interpret
from repro.kernels.nn_search import HIGHEST, NEG, _merge_topk

_IMAX = jnp.iinfo(jnp.int32).max


def _chunk_rows(bucket_cap: int, block: int) -> int:
    """Stage-2 chunk size: buckets are pow2 (< 128) or multiples of 128
    (see ann_index.build_ivf_index); pick the largest 128-multiple divisor
    of the capacity that fits the requested block."""
    if bucket_cap < 128:
        return bucket_cap
    m = bucket_cap // 128
    return 128 * max((d for d in range(1, m + 1)
                      if m % d == 0 and 128 * d <= block), default=1)


def ivf_chunk_plan(probes, bucket_occ, cpb: int, lb: int):
    """Per-query chunk schedule for the stage-2 grid.

    probes: (B, nprobe) bucket ids; bucket_occ: (C,) rows actually packed
    into each bucket (None = assume every bucket full). Returns
    ``(sel (B, nprobe*cpb) int32, nvalid (B,) int32)`` where ``sel`` holds
    each query's occupied chunk indices compacted to the front (the tail
    repeats the last valid chunk — same block index, so the pipeline skips
    the re-fetch) and ``nvalid`` is how many entries the kernel must merge.
    Bit-identical results to the dense plan by construction: every dropped
    chunk holds only -1-id padding slots, which score NEG and never win."""
    B, nprobe = probes.shape
    n_chunks = nprobe * cpb
    arange = jnp.arange(cpb, dtype=jnp.int32)
    cand = (probes[:, :, None] * cpb +
            arange[None, None, :]).reshape(B, n_chunks).astype(jnp.int32)
    if bucket_occ is None:
        return cand, jnp.full((B,), n_chunks, jnp.int32)
    occ = jnp.asarray(bucket_occ, jnp.int32)[probes]         # (B, nprobe)
    nch = jnp.minimum((occ + lb - 1) // lb, cpb)             # occupied chunks
    valid = (arange[None, None, :] < nch[:, :, None]).reshape(B, n_chunks)
    order = jnp.argsort(jnp.where(valid, 0, 1), axis=1)      # stable: valid
    sel = jnp.take_along_axis(cand, order, axis=1)           # first, in order
    nvalid = valid.sum(axis=1).astype(jnp.int32)
    last = jnp.take_along_axis(sel, jnp.maximum(nvalid - 1, 0)[:, None],
                               axis=1)
    j = jnp.arange(n_chunks, dtype=jnp.int32)[None, :]
    sel = jnp.where(j < nvalid[:, None], sel, last)
    return sel.astype(jnp.int32), nvalid


# ---------------------------------------------------------------------------
# stage 1: coarse quantizer probe
# ---------------------------------------------------------------------------

def ivf_probes(queries, centroids, nprobe: int):
    """Top-``nprobe`` partitions per query by centroid inner product.
    queries: (B, D); centroids: (C, D) -> (B, nprobe) int32."""
    nprobe = min(nprobe, centroids.shape[0])
    scores = jnp.matmul(queries.astype(jnp.float32),
                        centroids.T.astype(jnp.float32), precision=HIGHEST)
    _, probes = jax.lax.top_k(scores, nprobe)
    return probes.astype(jnp.int32)


# ---------------------------------------------------------------------------
# live re-rank (shared tail of both stage-2 implementations)
# ---------------------------------------------------------------------------

def _rerank_live(table, queries, ids):
    """Re-score candidate ids against the live table and sort descending.
    Invalid candidates (padding) come back as (-inf, -1)."""
    n = table.shape[0]
    valid = (ids >= 0) & (ids < n)
    rows = table[jnp.where(valid, ids, 0)].astype(jnp.float32)   # (B, k, D)
    s = jnp.einsum("bd,bkd->bk", queries.astype(jnp.float32), rows,
                   precision=HIGHEST)
    s = jnp.where(valid, s, -jnp.inf)
    order = jnp.argsort(-s, axis=-1)
    return (jnp.take_along_axis(s, order, axis=1),
            jnp.take_along_axis(jnp.where(valid, ids, -1), order, axis=1))


def _rerank_live_q(codes, qscale, qoffset, queries, ids):
    """``_rerank_live`` when the LIVE bank itself is int8-coded: gather
    winner codes + per-row affine, dequantize the (B, k, D) shortlist, and
    re-score — exact w.r.t. the quantized live values."""
    n = codes.shape[0]
    valid = (ids >= 0) & (ids < n)
    safe = jnp.where(valid, ids, 0)
    rows = (codes[safe].astype(jnp.float32) * qscale[safe][..., None]
            + qoffset[safe][..., None])                          # (B, k, D)
    s = jnp.einsum("bd,bkd->bk", queries.astype(jnp.float32), rows,
                   precision=HIGHEST)
    s = jnp.where(valid, s, -jnp.inf)
    order = jnp.argsort(-s, axis=-1)
    return (jnp.take_along_axis(s, order, axis=1),
            jnp.take_along_axis(jnp.where(valid, ids, -1), order, axis=1))


# ---------------------------------------------------------------------------
# stage 2, Pallas: scalar-prefetched bucket tiles + running top-k
# ---------------------------------------------------------------------------
#
# One kernel serves the dense, quantized and sharded layouts. Grid
# (S, B, chunks per shard): shard s of query i merges the chunk
# ``sel[i, s*cps + r]`` into a running top-k, resets it at r == 0 and
# flushes it at the shard's last chunk. The dense index is the S == 1 case.
#
# TPU tiling: every block's second-minor dimension is a multiple of 8. So
# queries are fetched as the (8, D) row group holding query i, packed ids
# and int8 scale/offset as (8, LB) groups of chunk rows (a (C*cap/LB, LB)
# view), and results are written back as (8, k) row groups; the kernel
# picks or replaces its one row with a sublane mask. Row groups are
# revisited on consecutive steps, so the query axis runs "arbitrary".

_SUB = 8
_SMEM_TABLE_BYTES = 512 * 1024


def _pick_row(x, r):
    """Row ``r`` of an (8, L) tile as (1, L), by a masked sublane sum."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(rows == r, x, jnp.zeros_like(x)), axis=0,
                   keepdims=True)


def _put_row(ref, r, val):
    """Overwrite row ``r`` of an (8, L) output block with ``val`` (1, L)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0)
    ref[...] = jnp.where(rows == r, val, ref[...])


def _ivf_kernel(sel_ref, nv_ref, q_ref, vec_ref, *refs, k: int, cps: int,
                quantized: bool):
    """Merge one bucket chunk into query i's running top-k for shard s.
    Int8 tiles are never dequantized: scores are ``s * (q . c) + o *
    sum(q)``, the exact decomposition of q against the dequantized rows,
    fused into the MXU dot + one VPU fixup."""
    if quantized:
        scl_ref, off_ref, id_ref, os_ref, oi_ref, bs_ref, bi_ref = refs
    else:
        id_ref, os_ref, oi_ref, bs_ref, bi_ref = refs
    s = pl.program_id(0)
    i = pl.program_id(1)
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _():
        bs_ref[...] = jnp.full_like(bs_ref, NEG)
        bi_ref[...] = jnp.full_like(bi_ref, _IMAX)

    # merge only this query's occupied chunks (ivf_chunk_plan); past-valid
    # steps re-see the last fetched block and skip the work entirely
    @pl.when(r < nv_ref[i, s])
    def _():
        row = sel_ref[i, s * cps + r] % _SUB
        q = _pick_row(q_ref[...].astype(jnp.float32), i % _SUB)  # (1, D)
        v = vec_ref[...].astype(jnp.float32)                     # (LB, D)
        scores = jax.lax.dot_general(q, v, (((1,), (1,)), ((), ())),
                                     precision=HIGHEST,
                                     preferred_element_type=jnp.float32)
        if quantized:
            scores = (scores * _pick_row(scl_ref[...], row)
                      + jnp.sum(q) * _pick_row(off_ref[...], row))
        ids = _pick_row(id_ref[...], row)                        # (1, LB)
        scores = jnp.where(ids >= 0, scores, NEG)
        ids = jnp.where(ids >= 0, ids, _IMAX)
        bs, bi = _merge_topk(scores, ids, bs_ref[...], bi_ref[...], k)
        bs_ref[...] = bs
        bi_ref[...] = bi

    @pl.when(r == cps - 1)
    def _():
        _put_row(os_ref, i % _SUB, bs_ref[...])
        _put_row(oi_ref, i % _SUB, bi_ref[...])


def _stage2_call(sel, nvalid, queries, vecs, ids, k: int, *, lb: int,
                 scale=None, offset=None, interpret: bool):
    """Run ``_ivf_kernel`` over a chunk schedule. sel: (B, S*cps) chunk
    indices; nvalid: (B, S) chunks to merge per (query, shard); vecs:
    (rows, D) packed rows; ids (and int8 ``scale``/``offset``): (rows,).
    Returns (scores, ids), each (B, S, k).

    The schedule is scalar-prefetched into SMEM (1 MiB on a v5e core), so
    a batch whose table exceeds ``_SMEM_TABLE_BYTES`` runs as several
    calls over row groups of queries."""
    B, D = queries.shape
    per_query = 4 * (sel.shape[1] + nvalid.shape[1])
    qb = max(_SUB, _SMEM_TABLE_BYTES // per_query // _SUB * _SUB)
    if B > qb:
        parts = [_stage2_call(sel[lo:lo + qb], nvalid[lo:lo + qb],
                              queries[lo:lo + qb], vecs, ids, k, lb=lb,
                              scale=scale, offset=offset,
                              interpret=interpret)
                 for lo in range(0, B, qb)]
        return tuple(jnp.concatenate(p, axis=0) for p in zip(*parts))
    S = nvalid.shape[1]
    cps = sel.shape[1] // S
    Bp = -(-B // _SUB) * _SUB
    # padded queries merge nothing (nvalid 0) and read chunk 0
    sel = jnp.pad(sel, ((0, Bp - B), (0, 0)))
    nvalid = jnp.pad(nvalid, ((0, Bp - B), (0, 0)))
    queries = jnp.pad(queries, ((0, Bp - B), (0, 0)))

    def chunk_rows(a, fill):
        a = a.reshape(-1, lb)
        n = a.shape[0]
        return jnp.pad(a, ((0, -(-n // _SUB) * _SUB - n), (0, 0)),
                       constant_values=fill)

    quantized = scale is not None
    per_slot = ([chunk_rows(scale, 1.0), chunk_rows(offset, 0.0)]
                if quantized else []) + [chunk_rows(ids, -1)]
    chunk = lambda s, i, r, sel: sel[i, s * cps + r]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Bp, cps),
        in_specs=[
            pl.BlockSpec((_SUB, D),
                         lambda s, i, r, sel, nv: (i // _SUB, 0)),
            pl.BlockSpec((lb, D),
                         lambda s, i, r, sel, nv: (chunk(s, i, r, sel), 0)),
        ] + [pl.BlockSpec((_SUB, lb), lambda s, i, r, sel, nv:
                          (chunk(s, i, r, sel) // _SUB, 0))] * len(per_slot),
        out_specs=[pl.BlockSpec((pl.Squeezed(), _SUB, k),
                                lambda s, i, r, sel, nv: (s, i // _SUB, 0))
                   ] * 2,
        scratch_shapes=[pltpu.VMEM((1, k), jnp.float32),
                        pltpu.VMEM((1, k), jnp.int32)],
    )
    out_s, out_i = pl.pallas_call(
        functools.partial(_ivf_kernel, k=k, cps=cps, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, Bp, k), jnp.float32),
                   jax.ShapeDtypeStruct((S, Bp, k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(sel, nvalid, queries, vecs, *per_slot)
    return (jnp.transpose(out_s[:, :B], (1, 0, 2)),
            jnp.transpose(out_i[:, :B], (1, 0, 2)))


def ivf_stage2_pallas(packed_vecs, packed_ids, queries, probes, k: int, *,
                      bucket_cap: int, bucket_occ=None, block: int = 256,
                      interpret: Optional[bool] = None):
    """packed_vecs: (C*cap, D); packed_ids: (C*cap,); queries: (B, D);
    probes: (B, nprobe) -> (scores (B, k), ids (B, k)), snapshot scores.
    ``bucket_occ`` (C,) enables the occupied-chunks-only schedule (see
    ``ivf_chunk_plan``) — same results, work proportional to occupancy."""
    lb = _chunk_rows(bucket_cap, block)
    assert bucket_cap % lb == 0, (bucket_cap, lb)
    # block-selector table + per-query valid count: chunk j of query i
    # reads packed block sel[i, j], merging only while j < nvalid[i]
    sel, nvalid = ivf_chunk_plan(probes, bucket_occ, bucket_cap // lb, lb)
    s, i = _stage2_call(sel, nvalid[:, None], queries, packed_vecs,
                        packed_ids, k, lb=lb,
                        interpret=resolve_interpret(interpret))
    return s[:, 0], i[:, 0]


def ivf_search_pallas(table, centroids, packed_vecs, packed_ids, queries,
                      k: int, nprobe: int, *, bucket_occ=None,
                      block: int = 256, interpret: Optional[bool] = None):
    """Full two-stage IVF search, Pallas stage 2. Returns (scores, ids)
    with live (re-ranked) scores; padding slots are (-inf, -1)."""
    bucket_cap = packed_vecs.shape[0] // centroids.shape[0]
    probes = ivf_probes(queries, centroids, nprobe)
    _, ids = ivf_stage2_pallas(packed_vecs, packed_ids, queries, probes, k,
                               bucket_cap=bucket_cap, bucket_occ=bucket_occ,
                               block=block, interpret=interpret)
    return _rerank_live(table, queries, ids)


# ---------------------------------------------------------------------------
# stage 2, Pallas, quantized: int8 bucket tiles with fused dequant scoring
# ---------------------------------------------------------------------------

def ivf_stage2_quantized_pallas(packed_codes, packed_scale, packed_offset,
                                packed_ids, queries, probes, k: int, *,
                                bucket_cap: int, bucket_occ=None,
                                block: int = 256,
                                interpret: Optional[bool] = None):
    """``ivf_stage2_pallas`` over a quantized index: packed_codes
    (C*cap, D) int8, packed_scale/packed_offset (C*cap,) f32. Snapshot
    scores are exact w.r.t. the quantized rows."""
    lb = _chunk_rows(bucket_cap, block)
    assert bucket_cap % lb == 0, (bucket_cap, lb)
    sel, nvalid = ivf_chunk_plan(probes, bucket_occ, bucket_cap // lb, lb)
    s, i = _stage2_call(sel, nvalid[:, None], queries, packed_codes,
                        packed_ids, k, lb=lb, scale=packed_scale,
                        offset=packed_offset,
                        interpret=resolve_interpret(interpret))
    return s[:, 0], i[:, 0]


def ivf_search_quantized_pallas(table_codes, qscale, qoffset, centroids,
                                packed_codes, packed_scale, packed_offset,
                                packed_ids, queries, k: int, nprobe: int, *,
                                bucket_occ=None, block: int = 256,
                                interpret: Optional[bool] = None):
    """Two-stage IVF search where BOTH the snapshot and the live bank are
    int8: quantized stage-2 shortlist, live re-rank against the dequantized
    winner rows (``_rerank_live_q``)."""
    bucket_cap = packed_codes.shape[0] // centroids.shape[0]
    probes = ivf_probes(queries, centroids, nprobe)
    _, ids = ivf_stage2_quantized_pallas(
        packed_codes, packed_scale, packed_offset, packed_ids, queries,
        probes, k, bucket_cap=bucket_cap, bucket_occ=bucket_occ,
        block=block, interpret=interpret)
    return _rerank_live_q(table_codes, qscale, qoffset, queries, ids)


# ---------------------------------------------------------------------------
# stage 2, Pallas, sharded: per-shard shortlists in one grid
# ---------------------------------------------------------------------------

def ivf_stage2_sharded_pallas(packed_vecs, packed_ids, queries, probes,
                              k: int, *, n_shards: int, nlist: int,
                              bucket_cap: int, bucket_occ=None,
                              block: int = 256,
                              interpret: Optional[bool] = None):
    """Per-shard stage-2 shortlists over a ``ShardedIVFIndex`` layout.

    packed_vecs: (S*C*cap, D) shard-major; probes: (B, S, nprobe) LOCAL
    bucket ids per shard. Returns (scores (B, S, k), ids (B, S, k)) —
    snapshot scores, global ids (the packed ids are global), NEG/_IMAX in
    unfilled slots. ``bucket_occ`` (S*C,) enables the occupied-chunk
    schedule per shard, exactly as in the dense kernel."""
    B = queries.shape[0]
    S, nprobe = probes.shape[1], probes.shape[2]
    lb = _chunk_rows(bucket_cap, block)
    assert bucket_cap % lb == 0, (bucket_cap, lb)
    cpb = bucket_cap // lb
    # globalize the bucket ids (shard s, local b -> s*nlist + b), then the
    # dense chunk planner runs unchanged on the flattened (B*S, nprobe)
    gprobes = (probes.astype(jnp.int32) +
               (jnp.arange(S, dtype=jnp.int32) * nlist)[None, :, None])
    sel, nvalid = ivf_chunk_plan(gprobes.reshape(B * S, nprobe),
                                 bucket_occ, cpb, lb)
    return _stage2_call(sel.reshape(B, S * nprobe * cpb),
                        nvalid.reshape(B, S), queries, packed_vecs,
                        packed_ids, k, lb=lb,
                        interpret=resolve_interpret(interpret))


def ivf_search_sharded_pallas(table, centroids, packed_vecs, packed_ids,
                              queries, k: int, nprobe: int, *,
                              n_shards: int, bucket_occ=None,
                              block: int = 256,
                              interpret: Optional[bool] = None):
    """Pallas counterpart of ``ivf_search_sharded_jnp`` (the bit-identical
    oracle): per-shard stage-1 probe, ONE sharded stage-2 pallas_call for
    every shard's shortlist, shard-major hierarchical merge, live re-rank.
    Single-device — the serving path for a sharded-layout index hosted on
    one core (the shard_map op remains the multi-device path)."""
    S = n_shards
    SC, D = centroids.shape
    C = SC // S
    cap = packed_vecs.shape[0] // SC
    B = queries.shape[0]
    nprobe = min(nprobe, C)
    qf = queries.astype(jnp.float32)
    cent = centroids.reshape(S, C, D)
    cscore = jnp.einsum("bd,scd->bsc", qf, cent.astype(jnp.float32),
                        precision=HIGHEST)
    _, probes = jax.lax.top_k(cscore, nprobe)               # (B, S, nprobe)
    ls, li = ivf_stage2_sharded_pallas(
        packed_vecs, packed_ids, queries, probes.astype(jnp.int32), k,
        n_shards=S, nlist=C, bucket_cap=cap, bucket_occ=bucket_occ,
        block=block, interpret=interpret)
    # hierarchical merge in shard-major order (== the oracle's concat);
    # _IMAX fill ids score NEG and fall to _rerank_live's invalid branch
    ls, li = ls.reshape(B, -1), li.reshape(B, -1)
    _, gsel = jax.lax.top_k(ls, min(k, ls.shape[1]))
    ids = jnp.take_along_axis(li, gsel, axis=1)
    return _rerank_live(table, queries, ids)


# ---------------------------------------------------------------------------
# sharded search, host reference (oracle for the shard_map op + benchmark)
# ---------------------------------------------------------------------------

def ivf_search_sharded_jnp(table, centroids, packed_vecs, packed_ids,
                           queries, k: int, nprobe: int, *, n_shards: int,
                           exclude_ids=None, packed_scale=None,
                           packed_offset=None):
    """Meshless reference of the sharded hierarchical IVF search.

    Takes a ``repro.core.ann_index.ShardedIVFIndex``'s flat shard-major
    arrays and simulates, on one device, exactly what
    ``repro.core.sharded_kb.sharded_kb_nn_search_ivf`` computes across a
    mesh: per-shard stage-1 probe of the shard's OWN centroids, per-shard
    stage-2 shortlist over its own buckets, per-shard top-k, shard-major
    concatenation (== the op's tiled all-gather order), global re-top-k,
    live re-rank. Bit-identical to the shard_map op on any mesh whose
    shard count matches (tests/test_sharded_ivf.py), and to the dense
    ``ivf_search_jnp`` when ``n_shards == 1``.

    ``packed_scale``/``packed_offset`` (both or neither): ``packed_vecs``
    holds int8 codes from a ``QuantizedShardedIVFIndex`` and the stage-2
    shortlist scores via the ``s (q.c) + o sum(q)`` decomposition; the
    live re-rank still runs against the fp32 ``table``, so quantization
    costs shortlist recall only.

    ``exclude_ids``: (B, E) int32, -1 entries inert — the shared
    ``overfetch_exclude_topk`` semantics, same as every other backend."""
    if exclude_ids is not None:
        from repro.kernels.nn_search import overfetch_exclude_topk
        return overfetch_exclude_topk(
            lambda kk: ivf_search_sharded_jnp(
                table, centroids, packed_vecs, packed_ids, queries, kk,
                nprobe, n_shards=n_shards, packed_scale=packed_scale,
                packed_offset=packed_offset),
            table.shape[0], k, exclude_ids)

    S = n_shards
    SC, D = centroids.shape
    C = SC // S
    cap = packed_vecs.shape[0] // SC
    B = queries.shape[0]
    nprobe = min(nprobe, C)
    qf = queries.astype(jnp.float32)
    cent = centroids.reshape(S, C, D)
    cscore = jnp.einsum("bd,scd->bsc", qf, cent.astype(jnp.float32),
                        precision=HIGHEST)
    _, probes = jax.lax.top_k(cscore, nprobe)               # (B, S, nprobe)
    sidx = jnp.arange(S)[None, :, None]
    cv = packed_vecs.reshape(S, C, cap, D)[sidx, probes]
    ci = packed_ids.reshape(S, C, cap)[sidx, probes].reshape(B, S, -1)
    s = jnp.einsum("bd,bsld->bsl", qf,
                   cv.reshape(B, S, nprobe * cap, D).astype(jnp.float32),
                   precision=HIGHEST)
    if packed_scale is not None:
        cs = packed_scale.reshape(S, C, cap)[sidx, probes].reshape(B, S, -1)
        co = packed_offset.reshape(S, C, cap)[sidx, probes].reshape(B, S, -1)
        s = s * cs + jnp.sum(qf, -1)[:, None, None] * co
    s = jnp.where(ci >= 0, s, NEG)
    kk = min(k, nprobe * cap)
    ls, sel = jax.lax.top_k(s, kk)                          # (B, S, kk)
    li = jnp.take_along_axis(ci, sel, axis=2)
    if kk < k:                  # degenerate tiny sub-index: pad per shard
        ls = jnp.pad(ls, ((0, 0), (0, 0), (0, k - kk)), constant_values=NEG)
        li = jnp.pad(li, ((0, 0), (0, 0), (0, k - kk)), constant_values=-1)
    ls, li = ls.reshape(B, -1), li.reshape(B, -1)           # shard-major
    _, gsel = jax.lax.top_k(ls, k)
    ids = jnp.take_along_axis(li, gsel, axis=1)
    return _rerank_live(table, queries, ids)


# ---------------------------------------------------------------------------
# stage 2, jnp reference (oracle + DenseBackend serving path)
# ---------------------------------------------------------------------------

def ivf_search_jnp(table, centroids, packed_vecs, packed_ids, queries,
                   k: int, nprobe: int):
    """Dense-gather reference of the two-stage search — the allclose oracle
    for ``ivf_search_pallas`` and the DenseBackend IVF path."""
    C = centroids.shape[0]
    cap = packed_vecs.shape[0] // C
    B, D = queries.shape
    probes = ivf_probes(queries, centroids, nprobe)
    cand_v = packed_vecs.reshape(C, cap, D)[probes].reshape(B, -1, D)
    cand_i = packed_ids.reshape(C, cap)[probes].reshape(B, -1)
    s = jnp.einsum("bd,bld->bl", queries.astype(jnp.float32),
                   cand_v.astype(jnp.float32), precision=HIGHEST)
    s = jnp.where(cand_i >= 0, s, NEG)
    L = cand_i.shape[1]
    if L < k:                                   # degenerate tiny index
        pad = k - L
        s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=NEG)
        cand_i = jnp.pad(cand_i, ((0, 0), (0, pad)), constant_values=-1)
    _, sel = jax.lax.top_k(s, k)
    ids = jnp.take_along_axis(cand_i, sel, axis=1)
    return _rerank_live(table, queries, ids)


def ivf_search_quantized_jnp(table_codes, qscale, qoffset, centroids,
                             packed_codes, packed_scale, packed_offset,
                             packed_ids, queries, k: int, nprobe: int):
    """Dense-gather reference of the fully-quantized two-stage search:
    int8 live bank (codes + per-row affine) and int8 snapshot. Stage-2
    scores via the decomposition, live re-rank via ``_rerank_live_q`` —
    the allclose oracle for ``ivf_search_quantized_pallas`` and the
    DenseBackend int8 IVF path."""
    C = centroids.shape[0]
    cap = packed_codes.shape[0] // C
    B, D = queries.shape
    qf = queries.astype(jnp.float32)
    probes = ivf_probes(queries, centroids, nprobe)
    cand_v = packed_codes.reshape(C, cap, D)[probes].reshape(B, -1, D)
    cand_i = packed_ids.reshape(C, cap)[probes].reshape(B, -1)
    cand_s = packed_scale.reshape(C, cap)[probes].reshape(B, -1)
    cand_o = packed_offset.reshape(C, cap)[probes].reshape(B, -1)
    s = jnp.einsum("bd,bld->bl", qf, cand_v.astype(jnp.float32),
                   precision=HIGHEST)
    s = s * cand_s + jnp.sum(qf, -1, keepdims=True) * cand_o
    s = jnp.where(cand_i >= 0, s, NEG)
    L = cand_i.shape[1]
    if L < k:                                   # degenerate tiny index
        pad = k - L
        s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=NEG)
        cand_i = jnp.pad(cand_i, ((0, 0), (0, pad)), constant_values=-1)
    _, sel = jax.lax.top_k(s, k)
    ids = jnp.take_along_axis(cand_i, sel, axis=1)
    return _rerank_live_q(table_codes, qscale, qoffset, queries, ids)
