"""Mesh-sharded Knowledge Bank — the engine's ``ShardedBackend`` substrate.

This is the TPU-native translation of the paper's "sharded and deployed in a
distributed fashion" bank (§3.2). Rows are sharded across EVERY mesh axis
(512-way on the multi-pod mesh). The RPC fan-out/fan-in of the original
becomes:

- lookup : each shard gathers the ids it owns (clamped local gather, zeros
           elsewhere) and the results are combined with one ``psum`` whose
           payload is O(B*K*D) — constant in the bank size N. Pending lazy
           gradients are applied owner-side first, fused into the same op.
- update / lazy_grad : owner-masked scatter, no communication at all.
- nn_search : per-shard blocked top-k (Pallas kernel on TPU), then an
           all-gather of the (B, k) candidate sets and a global re-top-k —
           the hierarchical ScaNN-sharding pattern, payload O(B*k*shards).
- nn_search (IVF) : each shard probes ITS OWN sub-index (per-shard k-means
           centroids + packed buckets from ``repro.core.ann_index.
           ShardedIVFIndex``), shortlists O(nprobe*cap) rows instead of its
           full N/S slice, and the same hierarchical merge combines the
           per-shard top-k. Winners are re-scored against the live sharded
           table (owner-masked gather + one psum, payload O(B*k*D)) so a
           stale snapshot costs recall, never score accuracy.

All owner-masked gather/scatter translation lives in ONE helper
(``OwnerShard``) instead of being re-derived per op: global ids become a
clamped gather index, a drop-masked scatter index, and an ownership mask.

Semantics are bit-identical to ``repro.core.knowledge_bank`` (the engine's
dense reference; enforced by tests/test_kb_engine.py and
tests/test_sharded_kb.py). The shared lazy-update math (``pending_delta``,
``lazy_grad_contribution``) is imported, never copied.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.knowledge_bank import (HIGHEST, KBState, ema_step,
                                       lazy_grad_contribution, pending_delta)
from repro.sharding.partition import DistContext


def kb_axes(dist: DistContext) -> Tuple[str, ...]:
    """Every mesh axis: the bank shards over all of them."""
    axes = (dist.data_axis, dist.model_axis)
    if dist.pod_axis:
        axes = (dist.pod_axis,) + axes
    return axes


def kb_pspecs(dist: DistContext) -> KBState:
    """PartitionSpec tree for a KBState on this mesh."""
    ax = kb_axes(dist)
    return KBState(table=P(ax, None), version=P(ax), grad_sum=P(ax, None),
                   grad_cnt=P(ax), grad_sqnorm=P(ax), norm_ema=P(ax),
                   step=P())


class OwnerShard:
    """This shard's view of the global row space — the single copy of the
    owner-masked gather/scatter pattern every sharded op is built from.

    For a shard owning rows ``[offset, offset + n_local)`` and a replicated
    flat id vector, precomputes:

    - ``mine``: ownership mask per id
    - ``gid`` : clamped local index, safe for gathers (foreign lanes read
                garbage that the caller masks with ``mine``)
    - ``sid`` : local index with foreign lanes pushed out of bounds, so
                ``mode="drop"`` scatters silently skip them
    """

    def __init__(self, n_local: int, axes: Tuple[str, ...],
                 flat_ids: Optional[jnp.ndarray] = None):
        idx = 0
        for a in axes:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        self.n_local = n_local
        self.offset = idx * n_local
        if flat_ids is not None:
            lid = flat_ids - self.offset
            self.mine = (lid >= 0) & (lid < n_local)
            self.gid = jnp.clip(lid, 0, n_local - 1)
            self.sid = jnp.where(self.mine, lid, n_local)

    def gather(self, arr):
        return arr[self.gid]

    def set(self, arr, vals):
        """Owner-masked scatter-set; foreign lanes dropped."""
        return arr.at[self.sid].set(vals.astype(arr.dtype), mode="drop")

    def add(self, arr, vals):
        """Owner-masked scatter-add; foreign lanes dropped."""
        return arr.at[self.sid].add(vals.astype(arr.dtype), mode="drop")

    def bump(self, arr, inc):
        """Gather-increment-scatter: +inc once per touched row per call,
        deterministic under duplicate ids (matches dense semantics)."""
        return self.set(arr, self.gather(arr) + inc)

    def mask(self, vals, fill=0.0):
        """Zero (or ``fill``) the lanes this shard does not own."""
        m = self.mine
        return jnp.where(m[:, None] if vals.ndim == 2 else m, vals, fill)


# ---------------------------------------------------------------------------
# lookup (+ fused lazy apply)
# ---------------------------------------------------------------------------

def sharded_kb_lookup(kb: KBState, ids: jnp.ndarray, dist: DistContext, *,
                      lazy_lr: float = 0.1, zmax: float = 3.0,
                      apply_pending: bool = True):
    """ids: any shape, replicated. Returns (values (..., D) replicated, kb')."""
    axes = kb_axes(dist)
    specs = kb_pspecs(dist)

    def body(table, version, gsum, gcnt, gsq, ids):
        own = OwnerShard(table.shape[0], axes, ids.reshape(-1))
        rows = own.gather(table).astype(jnp.float32)
        if apply_pending:
            cnt = own.gather(gcnt)
            delta = pending_delta(own.gather(gsum), cnt, own.gather(gsq),
                                  lazy_lr=lazy_lr, zmax=zmax)
            rows = rows + own.mask(delta)
            table = own.set(table, rows)
            version = own.bump(version, (cnt > 0).astype(jnp.int32))
            gsum = own.set(gsum, jnp.zeros_like(rows))
            gcnt = own.set(gcnt, jnp.zeros_like(cnt))
            gsq = own.set(gsq, jnp.zeros_like(cnt))
            # reply with the rows as stored: XLA may fuse the arithmetic
            # above into both the scatter and the psum's operand and round
            # the two copies differently (an FMA in one fusion only)
            rows = own.gather(table)
        vals = jax.lax.psum(own.mask(rows), axes)
        return vals, table, version, gsum, gcnt, gsq

    vals, table, version, gsum, gcnt, gsq = jax.shard_map(
        body, mesh=dist.mesh,
        in_specs=(specs.table, specs.version, specs.grad_sum, specs.grad_cnt,
                  specs.grad_sqnorm, P(*([None] * ids.ndim))),
        out_specs=(P(None, None), specs.table, specs.version,
                   specs.grad_sum, specs.grad_cnt, specs.grad_sqnorm),
        check_vma=False,
    )(kb.table, kb.version, kb.grad_sum, kb.grad_cnt, kb.grad_sqnorm, ids)
    vals = vals.reshape(*ids.shape, -1)
    return vals, kb._replace(table=table, version=version, grad_sum=gsum,
                             grad_cnt=gcnt, grad_sqnorm=gsq)


# ---------------------------------------------------------------------------
# update / lazy grad (owner-masked scatter, zero communication)
# ---------------------------------------------------------------------------

def sharded_kb_update(kb: KBState, ids, values, dist: DistContext) -> KBState:
    axes = kb_axes(dist)
    specs = kb_pspecs(dist)

    def body(table, version, gsum, gcnt, gsq, ids, values):
        flat = ids.reshape(-1)
        vals = values.reshape(flat.shape[0], -1)
        own = OwnerShard(table.shape[0], axes, flat)
        zero = jnp.zeros((flat.shape[0],), jnp.float32)
        return (own.set(table, vals),
                own.bump(version, 1),
                own.set(gsum, jnp.zeros_like(vals)),
                own.set(gcnt, zero),
                own.set(gsq, zero))

    table, version, gsum, gcnt, gsq = jax.shard_map(
        body, mesh=dist.mesh,
        in_specs=(specs.table, specs.version, specs.grad_sum, specs.grad_cnt,
                  specs.grad_sqnorm, P(*([None] * ids.ndim)),
                  P(*([None] * values.ndim))),
        out_specs=(specs.table, specs.version, specs.grad_sum,
                   specs.grad_cnt, specs.grad_sqnorm),
        check_vma=False,
    )(kb.table, kb.version, kb.grad_sum, kb.grad_cnt, kb.grad_sqnorm, ids,
      values)
    return kb._replace(table=table, version=version, grad_sum=gsum,
                       grad_cnt=gcnt, grad_sqnorm=gsq, step=kb.step + 1)


def sharded_kb_lazy_grad(kb: KBState, ids, grads, dist: DistContext,
                         *, zmax: float = 0.0,
                         mask: Optional[jnp.ndarray] = None) -> KBState:
    axes = kb_axes(dist)
    specs = kb_pspecs(dist)

    def body(gsum, gcnt, gsq, ema, ids, grads, *opt):
        flat = ids.reshape(-1)
        g = grads.reshape(flat.shape[0], -1).astype(jnp.float32)
        own = OwnerShard(gsum.shape[0], axes, flat)
        sq = jnp.sum(g * g, -1)
        g, sq = lazy_grad_contribution(g, sq, own.gather(ema), zmax=zmax)
        w = opt[0].reshape(-1) if opt else jnp.ones_like(sq)
        sq_sum = own.add(jnp.zeros_like(ema), sq * w)
        cnt_in = own.add(jnp.zeros_like(ema), w)
        return (own.add(gsum, g * w[:, None]),
                own.add(gcnt, w),
                own.add(gsq, sq * w),
                ema_step(ema, sq_sum, cnt_in))

    in_specs = (specs.grad_sum, specs.grad_cnt, specs.grad_sqnorm,
                specs.norm_ema, P(*([None] * ids.ndim)),
                P(*([None] * grads.ndim)))
    args = (kb.grad_sum, kb.grad_cnt, kb.grad_sqnorm, kb.norm_ema, ids, grads)
    if mask is not None:
        in_specs = in_specs + (P(*([None] * mask.ndim)),)
        args = args + (mask,)
    gsum, gcnt, gsq, ema = jax.shard_map(
        body, mesh=dist.mesh, in_specs=in_specs,
        out_specs=(specs.grad_sum, specs.grad_cnt, specs.grad_sqnorm,
                   specs.norm_ema),
        check_vma=False,
    )(*args)
    return kb._replace(grad_sum=gsum, grad_cnt=gcnt, grad_sqnorm=gsq,
                       norm_ema=ema)


def sharded_kb_flush(kb: KBState, dist: DistContext, *, lazy_lr: float = 0.1,
                     zmax: float = 3.0) -> KBState:
    """Expiration path: apply every shard's pending cache locally — embar-
    rassingly parallel, zero communication (each shard owns its rows)."""
    specs = kb_pspecs(dist)

    def body(table, version, gsum, gcnt, gsq):
        delta = pending_delta(gsum, gcnt, gsq, lazy_lr=lazy_lr, zmax=zmax)
        table = (table.astype(jnp.float32) + delta).astype(table.dtype)
        version = version + (gcnt > 0).astype(jnp.int32)
        return (table, version, jnp.zeros_like(gsum), jnp.zeros_like(gcnt),
                jnp.zeros_like(gsq))

    table, version, gsum, gcnt, gsq = jax.shard_map(
        body, mesh=dist.mesh,
        in_specs=(specs.table, specs.version, specs.grad_sum, specs.grad_cnt,
                  specs.grad_sqnorm),
        out_specs=(specs.table, specs.version, specs.grad_sum,
                   specs.grad_cnt, specs.grad_sqnorm),
        check_vma=False,
    )(kb.table, kb.version, kb.grad_sum, kb.grad_cnt, kb.grad_sqnorm)
    return kb._replace(table=table, version=version, grad_sum=gsum,
                       grad_cnt=gcnt, grad_sqnorm=gsq, step=kb.step + 1)


# ---------------------------------------------------------------------------
# hierarchical nn search
# ---------------------------------------------------------------------------

def sharded_kb_nn_search(kb: KBState, queries, k: int, dist: DistContext,
                         use_kernel: bool = False):
    """queries: (B, D) replicated -> (scores (B,k), ids (B,k)) replicated.
    Local top-k per shard, all-gather of candidates, global re-top-k."""
    axes = kb_axes(dist)
    specs = kb_pspecs(dist)

    def body(table, queries):
        own = OwnerShard(table.shape[0], axes)
        kk = min(k, own.n_local)
        if use_kernel:
            from repro.kernels.ops import nn_search_topk
            ls, li = nn_search_topk(queries, table, kk)
        else:
            scores = jnp.matmul(queries.astype(jnp.float32),
                                table.T.astype(jnp.float32),
                                precision=HIGHEST)
            ls, li = jax.lax.top_k(scores, kk)
        li = li + own.offset
        # gather candidates from every shard: (B, k*n_shards)
        for a in axes:
            ls = jax.lax.all_gather(ls, a, axis=1, tiled=True)
            li = jax.lax.all_gather(li, a, axis=1, tiled=True)
        gs, gi = jax.lax.top_k(ls, k)
        ids = jnp.take_along_axis(li, gi, axis=1)
        return gs, ids

    return jax.shard_map(
        body, mesh=dist.mesh,
        in_specs=(specs.table, P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(kb.table, queries)


def sharded_kb_nn_search_ivf(table, centroids, packed_vecs, packed_ids,
                             queries, k: int, nprobe: int, dist: DistContext,
                             *, exclude_ids=None, packed_scale=None,
                             packed_offset=None):
    """Sharded two-stage IVF search with hierarchical top-k merge.

    ``table``: the live (N, D) bank; ``centroids``/``packed_vecs``/
    ``packed_ids``: a ``ShardedIVFIndex`` snapshot whose shard-major layout
    is sharded over the same row axes as the table, so each shard's local
    block is its own complete sub-index (global ids, -1 padding). Per
    query, every shard probes its ``nprobe`` best local buckets — stage-2
    work O(nprobe*cap*D) per shard instead of O(N/S*D) — keeps a local
    running top-k, and the (B, k)-per-shard shortlists meet in an
    all-gather + global re-top-k, the same O(B*k*S) fan-in as the exact
    sharded path. The k winners are re-scored against the LIVE table
    (owner-masked gather, one psum), so returned scores are exact even when
    the snapshot is stale.

    Determinism contract: a pure function of (index, table, queries) — no
    RNG, no data-dependent shapes — so coalescing a batch of sharded-IVF
    searches into one call returns exactly what each search returns solo.
    ``exclude_ids`` (B, E) int32, -1 = no-op: over-fetches k+E candidates
    and masks post-merge, matching the dense pre-mask semantics whenever
    the shortlist holds k survivors.

    ``packed_scale``/``packed_offset`` (both or neither): the snapshot is
    a ``QuantizedShardedIVFIndex`` — ``packed_vecs`` holds int8 codes and
    the stage-2 shortlist scores via the exact ``s (q.c) + o sum(q)``
    decomposition. The live re-rank still gathers the fp32 table, so
    index quantization costs shortlist recall, never final scores."""
    from repro.kernels.nn_search import NEG, overfetch_exclude_topk
    if exclude_ids is not None:
        return overfetch_exclude_topk(
            lambda kk: sharded_kb_nn_search_ivf(
                table, centroids, packed_vecs, packed_ids, queries, kk,
                nprobe, dist, packed_scale=packed_scale,
                packed_offset=packed_offset),
            table.shape[0], k, exclude_ids)

    axes = kb_axes(dist)
    specs = kb_pspecs(dist)
    n_shards = int(np.prod([dist.mesh.shape[a] for a in axes]))
    C_local = centroids.shape[0] // n_shards
    nprobe = min(nprobe, C_local)
    B, D = queries.shape
    quantized = packed_scale is not None

    def body(table, cent, pvec, pid, q, *qargs):
        C = cent.shape[0]
        cap = pvec.shape[0] // C
        qf = q.astype(jnp.float32)
        # stage 1: probe this shard's own coarse quantizer
        cscore = jnp.matmul(qf, cent.T.astype(jnp.float32),
                            precision=HIGHEST)               # (B, C)
        _, probes = jax.lax.top_k(cscore, nprobe)
        # stage 2: score only the probed buckets (local shortlist)
        cv = pvec.reshape(C, cap, D)[probes].reshape(B, nprobe * cap, D)
        ci = pid.reshape(C, cap)[probes].reshape(B, nprobe * cap)
        s = jnp.einsum("bd,bld->bl", qf, cv.astype(jnp.float32),
                       precision=HIGHEST)
        if qargs:       # int8 codes: exact dequantized-score decomposition
            pscl, poff = qargs
            cs = pscl.reshape(C, cap)[probes].reshape(B, nprobe * cap)
            co = poff.reshape(C, cap)[probes].reshape(B, nprobe * cap)
            s = s * cs + jnp.sum(qf, -1, keepdims=True) * co
        s = jnp.where(ci >= 0, s, NEG)
        # quantized shortlists over-retrieve 4x so the exact fp32 live
        # re-rank can recover near-ties the int8 scores mis-ordered;
        # fp32 keeps kq == k, leaving that path bit-identical
        kq = 4 * k if qargs else k
        kk = min(kq, nprobe * cap)
        ls, sel = jax.lax.top_k(s, kk)
        li = jnp.take_along_axis(ci, sel, axis=1)
        if kk < kq:         # degenerate tiny sub-index: pad the shortlist
            ls = jnp.pad(ls, ((0, 0), (0, kq - kk)), constant_values=NEG)
            li = jnp.pad(li, ((0, 0), (0, kq - kk)), constant_values=-1)
        # hierarchical merge: gather every shard's shortlist, re-top-k.
        # REVERSED axis order so the concatenation is shard-id-major
        # (OwnerShard numbers shards first-axis-major; gathering the last
        # axis first nests it innermost) — keeps the merged candidate
        # order, and therefore top-k tie-breaking, bit-identical to the
        # meshless ivf_search_sharded_jnp reference on multi-axis meshes
        for a in reversed(axes):
            ls = jax.lax.all_gather(ls, a, axis=1, tiled=True)
            li = jax.lax.all_gather(li, a, axis=1, tiled=True)
        _, gsel = jax.lax.top_k(ls, kq)
        ids = jnp.take_along_axis(li, gsel, axis=1)
        # live re-rank: owner-masked gather + psum (payload O(B*kq*D))
        valid = ids >= 0
        own = OwnerShard(table.shape[0], axes,
                         jnp.where(valid, ids, 0).reshape(-1))
        rows = jax.lax.psum(
            own.mask(own.gather(table).astype(jnp.float32)), axes)
        s_live = jnp.einsum("bd,bkd->bk", qf, rows.reshape(B, kq, D),
                            precision=HIGHEST)
        s_live = jnp.where(valid, s_live, -jnp.inf)
        order = jnp.argsort(-s_live, axis=-1)[:, :k]
        return (jnp.take_along_axis(s_live, order, axis=1),
                jnp.take_along_axis(jnp.where(valid, ids, -1), order,
                                    axis=1))

    idx_spec = P(axes, None)
    in_specs = (specs.table, idx_spec, idx_spec, P(axes), P(None, None))
    args = (table, centroids, packed_vecs, packed_ids, queries)
    if quantized:
        in_specs = in_specs + (P(axes), P(axes))
        args = args + (packed_scale, packed_offset)
    return jax.shard_map(
        body, mesh=dist.mesh,
        in_specs=in_specs,
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(*args)
