"""Knowledge Bank (paper §3.2): the dense reference semantics layer.

This module is the *semantics ground truth* of the pluggable KB engine
(``repro.core.kb_engine``). It defines the shared ``KBState`` and the
functional ops every backend must agree with bit-for-bit:

- feature lookup      : ``FeatureStore`` (neighbor ids/weights, labels)
- embedding lookup/update with back-propagated gradients (DynamicEmbedding-
  style): ``kb_lookup`` / ``kb_update`` / ``kb_lazy_grad`` / ``kb_flush``
- nearest-neighbor lookup: ``kb_nn_search``

Lazy update semantics (faithful to §3.2): gradients arriving from (possibly
many) trainers are cached (sum + count + squared-norm stats), and applied as
the *average of all cached gradients with outlier detection* at the next
lookup of that row — or en masse by ``kb_flush`` (the "expiration" path).
Outlier detection keeps O(1) state per row: the averaged gradient's norm is
clipped at ``zmax * sqrt(mean per-contribution squared norm)``, rejecting
update mass contributed by abnormally large cached gradients.

Batched-call invariants (what makes server-side request coalescing legal —
see ``repro.core.async_runtime``):

- ops are *deterministic under duplicate ids* within one call: lookups of a
  repeated id return identical rows, version counters bump once per touched
  row per call (gather-increment-scatter, not per-occurrence add), and
  ``kb_lazy_grad`` accumulates per occurrence as before;
- ``kb_lazy_grad`` takes an optional per-entry 0/1 ``mask`` so a batch can
  be padded to a fixed jit bucket size without the padding contributing.

The three engine backends build on this layer: ``DenseBackend`` calls these
ops directly, ``repro.core.sharded_kb`` re-expresses them as owner-masked
shard_map ops, and the Pallas backend calls them for lookups and writes
and runs blocked kernels for the ops that scan the whole bank
(``kb_flush``, ``kb_nn_search``).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class KBState(NamedTuple):
    table: jnp.ndarray          # (N, D)
    version: jnp.ndarray        # (N,) int32 — bumped on every write
    grad_sum: jnp.ndarray       # (N, D) f32 — cached gradient sum
    grad_cnt: jnp.ndarray       # (N,) f32 — number of cached gradients
    grad_sqnorm: jnp.ndarray    # (N,) f32 — sum of per-gradient sq norms
    norm_ema: jnp.ndarray       # (N,) f32 — EMA of contribution sq norms
    step: jnp.ndarray           # () int32 — bank clock


_EMA_DECAY = 0.9
# the bank stores fp32 rows, so its score matmuls run at full fp32 precision
# on every platform (the TPU default rounds f32 operands through bf16)
HIGHEST = jax.lax.Precision.HIGHEST


class FeatureStore(NamedTuple):
    """Paper's 'feature lookup': per-instance features keyed by id."""
    nbr_ids: jnp.ndarray        # (N, K) int32, -1 = missing
    nbr_weights: jnp.ndarray    # (N, K) f32
    labels: jnp.ndarray         # (N,) int32, -1 = unlabeled
    label_conf: jnp.ndarray     # (N,) f32 — confidence of (mined) labels


def kb_create(num_entries: int, dim: int, *, dtype=jnp.float32,
              key: Optional[jax.Array] = None) -> KBState:
    if key is not None:
        table = (jax.random.normal(key, (num_entries, dim), jnp.float32)
                 * 0.01).astype(dtype)
    else:
        table = jnp.zeros((num_entries, dim), dtype)
    return KBState(
        table=table,
        version=jnp.zeros((num_entries,), jnp.int32),
        grad_sum=jnp.zeros((num_entries, dim), jnp.float32),
        grad_cnt=jnp.zeros((num_entries,), jnp.float32),
        grad_sqnorm=jnp.zeros((num_entries,), jnp.float32),
        norm_ema=jnp.zeros((num_entries,), jnp.float32),
        step=jnp.int32(0),
    )


def feature_store_create(num_entries: int, max_neighbors: int) -> FeatureStore:
    return FeatureStore(
        nbr_ids=jnp.full((num_entries, max_neighbors), -1, jnp.int32),
        nbr_weights=jnp.zeros((num_entries, max_neighbors), jnp.float32),
        labels=jnp.full((num_entries,), -1, jnp.int32),
        label_conf=jnp.zeros((num_entries,), jnp.float32),
    )


# ---------------------------------------------------------------------------
# lazy-update math (shared with sharded_kb)
# ---------------------------------------------------------------------------

def pending_delta(grad_sum, grad_cnt, grad_sqnorm, *, lazy_lr: float,
                  zmax: float):
    """The update each row would receive if its cache were applied now.

    Average of cached gradients, norm-clipped at zmax * rms contribution
    norm (outlier rejection). Rows with an empty cache get zero."""
    cnt = jnp.maximum(grad_cnt, 1.0)[..., None]
    avg = grad_sum / cnt
    avg_norm = jnp.linalg.norm(avg, axis=-1, keepdims=True)
    rms = jnp.sqrt(grad_sqnorm / jnp.maximum(grad_cnt, 1.0))[..., None]
    cap = zmax * jnp.maximum(rms, 1e-12)
    scale = jnp.minimum(1.0, cap / jnp.maximum(avg_norm, 1e-12))
    delta = -lazy_lr * avg * scale
    return jnp.where((grad_cnt > 0)[..., None], delta, 0.0)


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def kb_lookup(kb: KBState, ids: jnp.ndarray, *, lazy_lr: float = 0.1,
              zmax: float = 3.0, apply_pending: bool = True
              ) -> Tuple[jnp.ndarray, KBState]:
    """Fetch rows ``ids`` (any shape). If ``apply_pending``, first applies the
    lazily-cached gradient average to those rows (paper: "caching the results
    of gradient update until the next lookup request arrives")."""
    flat = ids.reshape(-1)
    if apply_pending:
        delta = pending_delta(kb.grad_sum[flat], kb.grad_cnt[flat],
                              kb.grad_sqnorm[flat], lazy_lr=lazy_lr,
                              zmax=zmax)
        new_rows = kb.table[flat].astype(jnp.float32) + delta
        table = kb.table.at[flat].set(new_rows.astype(kb.table.dtype))
        kb = kb._replace(
            table=table,
            grad_sum=kb.grad_sum.at[flat].set(0.0),
            grad_cnt=kb.grad_cnt.at[flat].set(0.0),
            grad_sqnorm=kb.grad_sqnorm.at[flat].set(0.0),
            # gather-increment-scatter: +1 per touched row per call, exactly
            # once even when ids repeat (duplicate writes carry equal values)
            version=kb.version.at[flat].set(
                kb.version[flat] + (kb.grad_cnt[flat] > 0).astype(jnp.int32)),
        )
        vals = new_rows.reshape(*ids.shape, -1)
    else:
        vals = kb.table[flat].astype(jnp.float32).reshape(*ids.shape, -1)
    return vals, kb


def kb_update(kb: KBState, ids: jnp.ndarray, values: jnp.ndarray) -> KBState:
    """Direct write (knowledge-maker push). ids: (...,); values: (..., D).
    Cached gradients for overwritten rows are discarded (they were computed
    against stale values)."""
    flat = ids.reshape(-1)
    vals = values.reshape(flat.shape[0], -1)
    return kb._replace(
        table=kb.table.at[flat].set(vals.astype(kb.table.dtype)),
        version=kb.version.at[flat].set(kb.version[flat] + 1),
        grad_sum=kb.grad_sum.at[flat].set(0.0),
        grad_cnt=kb.grad_cnt.at[flat].set(0.0),
        grad_sqnorm=kb.grad_sqnorm.at[flat].set(0.0),
        step=kb.step + 1,
    )


def lazy_grad_contribution(g, sq, ema, *, zmax: float):
    """Entry-side outlier clip of one gradient batch against the persistent
    norm EMA (shared by every backend). Returns clipped (g', sq')."""
    if zmax and zmax > 0:
        cap = zmax * jnp.sqrt(jnp.maximum(ema, 1e-30))
        nrm = jnp.sqrt(jnp.maximum(sq, 1e-30))
        scale = jnp.where(ema > 0, jnp.minimum(1.0, cap / nrm), 1.0)
        g = g * scale[:, None]
        sq = sq * scale * scale
    return g, sq


def ema_step(ema, sq_sum, cnt):
    """One norm-EMA step per row per call, against the mean clipped squared
    norm of the call's contributions (``sq_sum / cnt``). Rows with no
    contribution keep their EMA. One step per CALL (not per occurrence)
    keeps the update deterministic and bounded under duplicate ids —
    exactly what a coalesced multi-client batch produces."""
    mean_sq = sq_sum / jnp.maximum(cnt, 1.0)
    return jnp.where(cnt > 0,
                     jnp.where(ema > 0,
                               _EMA_DECAY * ema + (1 - _EMA_DECAY) * mean_sq,
                               mean_sq),
                     ema)


def kb_lazy_grad(kb: KBState, ids: jnp.ndarray, grads: jnp.ndarray,
                 *, zmax: float = 0.0,
                 mask: Optional[jnp.ndarray] = None) -> KBState:
    """Cache gradients w.r.t. looked-up rows. ids: (...,); grads (..., D).
    Duplicate ids accumulate (each counts as one cached gradient); the
    norm EMA advances one step per touched row per call (see ``ema_step``).

    Entry-side outlier detection (``zmax > 0``): each incoming gradient's
    norm is clipped at ``zmax * sqrt(norm_ema)`` — a persistent EMA of
    per-contribution squared norms — so a single corrupted trainer cannot
    poison the cached average (§3.2 "average of all cached gradients with
    possible outlier detection").

    ``mask`` (flat 0/1 per entry): entries with mask 0 contribute nothing —
    this is what lets the coalescing server pad a merged batch to a fixed
    jit bucket size with throwaway entries."""
    flat = ids.reshape(-1)
    g = grads.reshape(flat.shape[0], -1).astype(jnp.float32)
    sq = jnp.sum(g * g, axis=-1)
    g, sq = lazy_grad_contribution(g, sq, kb.norm_ema[flat], zmax=zmax)
    w = jnp.ones_like(sq) if mask is None else mask.reshape(-1)
    sq_sum = jnp.zeros_like(kb.norm_ema).at[flat].add(sq * w)
    cnt_in = jnp.zeros_like(kb.norm_ema).at[flat].add(w)
    return kb._replace(
        grad_sum=kb.grad_sum.at[flat].add(g * w[:, None]),
        grad_cnt=kb.grad_cnt.at[flat].add(w),
        grad_sqnorm=kb.grad_sqnorm.at[flat].add(sq * w),
        norm_ema=ema_step(kb.norm_ema, sq_sum, cnt_in),
    )


def kb_flush(kb: KBState, *, lazy_lr: float = 0.1, zmax: float = 3.0
             ) -> KBState:
    """Expiration path: apply every pending cached gradient now."""
    delta = pending_delta(kb.grad_sum, kb.grad_cnt, kb.grad_sqnorm,
                          lazy_lr=lazy_lr, zmax=zmax)
    return kb._replace(
        table=(kb.table.astype(jnp.float32) + delta).astype(kb.table.dtype),
        version=kb.version + (kb.grad_cnt > 0).astype(jnp.int32),
        grad_sum=jnp.zeros_like(kb.grad_sum),
        grad_cnt=jnp.zeros_like(kb.grad_cnt),
        grad_sqnorm=jnp.zeros_like(kb.grad_sqnorm),
        step=kb.step + 1,
    )


def kb_nn_search(kb: KBState, queries: jnp.ndarray, k: int,
                 *, exclude_ids: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k maximum-inner-product search over the whole bank.

    queries: (B, D) -> (scores (B, k), ids (B, k)). Reference path; the
    blocked Pallas kernel lives in repro.kernels.nn_search."""
    scores = jnp.matmul(queries.astype(jnp.float32),
                        kb.table.T.astype(jnp.float32), precision=HIGHEST)
    if exclude_ids is not None:
        B = queries.shape[0]
        excl = jnp.zeros(scores.shape, bool).at[
            jnp.arange(B)[:, None], exclude_ids].set(
            exclude_ids >= 0, mode="drop")
        scores = jnp.where(excl, -jnp.inf, scores)
    return jax.lax.top_k(scores, k)


# ---------------------------------------------------------------------------
# quantized storage: int8 codes + per-row affine (scale, offset)
# ---------------------------------------------------------------------------
#
# A row x is stored as int8 codes c with fp32 (scale s, offset o) such that
# dequant(c) = c * s + o. Quantization maps the row's [min, max] onto the
# symmetric code range [-127, 127]:
#
#     o = (max + min) / 2        s = (max - min) / 254
#
# so the max element always lands exactly on code +127 and the min on -127.
# That symmetry is what makes re-quantizing a dequantized row reproduce the
# SAME codes (hi' = o + 127 s, lo' = o - 127 s => o' = o, s' = s): untouched
# rows never drift, and a repeat lookup returns bit-identical values — the
# invariant the server's hot-id cache relies on.
#
# MIPS against quantized rows never materializes the dequantized matrix:
#
#     q . (c s + o) = s (q . c) + o sum(q)
#
# (``quantized_scores``) — exact w.r.t. the quantized values, so scoring
# the shortlist quantized costs recall only; the engine re-ranks winners
# against fp32 masters so final scores stay exact where masters exist.

def quantize_rows(vals: jnp.ndarray):
    """Per-row affine int8 quantization. vals: (..., D) -> (codes int8,
    scale (...,) f32, offset (...,) f32). Constant rows (max == min) get
    scale 1 / codes 0, so dequant returns the constant exactly."""
    vals = vals.astype(jnp.float32)
    hi = jnp.max(vals, axis=-1)
    lo = jnp.min(vals, axis=-1)
    offset = 0.5 * (hi + lo)
    scale = (hi - lo) / 254.0
    scale = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(
        jnp.round((vals - offset[..., None]) / scale[..., None]),
        -127, 127).astype(jnp.int8)
    return codes, scale, offset


def dequantize_rows(codes: jnp.ndarray, scale: jnp.ndarray,
                    offset: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``quantize_rows``: (..., D) int8 -> (..., D) f32."""
    return (codes.astype(jnp.float32) * scale[..., None]
            + offset[..., None])


def quantized_scores(queries: jnp.ndarray, codes: jnp.ndarray,
                     scale: jnp.ndarray, offset: jnp.ndarray) -> jnp.ndarray:
    """MIPS scores against quantized rows without dequantizing the bank:
    ``s * (q . c) + o * sum(q)``. queries: (B, D); codes: (N, D) ->
    (B, N) f32, exact w.r.t. the quantized values."""
    qf = queries.astype(jnp.float32)
    raw = jnp.matmul(qf, codes.T.astype(jnp.float32),
                     precision=HIGHEST)                      # (B, N)
    return raw * scale[None, :] + jnp.sum(qf, -1, keepdims=True) * offset


def kb_lookup_q(kb: KBState, qscale: jnp.ndarray, qoffset: jnp.ndarray,
                ids: jnp.ndarray, *, lazy_lr: float = 0.1, zmax: float = 3.0,
                apply_pending: bool = True):
    """``kb_lookup`` for an int8-coded table with side-car (scale, offset).

    Returns (vals f32, kb', qscale', qoffset'). Rows WITH pending cached
    gradients dequantize, apply the clipped average, and re-quantize; rows
    without keep their exact codes (no re-quantization drift). The returned
    values are the dequantization of what the bank now stores, so a repeat
    lookup without intervening writes is bit-identical."""
    flat = ids.reshape(-1)
    rows = dequantize_rows(kb.table[flat], qscale[flat], qoffset[flat])
    if not apply_pending:
        return rows.reshape(*ids.shape, -1), kb, qscale, qoffset
    delta = pending_delta(kb.grad_sum[flat], kb.grad_cnt[flat],
                          kb.grad_sqnorm[flat], lazy_lr=lazy_lr, zmax=zmax)
    codes_n, s_n, o_n = quantize_rows(rows + delta)
    upd = kb.grad_cnt[flat] > 0
    codes_w = jnp.where(upd[:, None], codes_n, kb.table[flat])
    s_w = jnp.where(upd, s_n, qscale[flat])
    o_w = jnp.where(upd, o_n, qoffset[flat])
    kb = kb._replace(
        table=kb.table.at[flat].set(codes_w),
        grad_sum=kb.grad_sum.at[flat].set(0.0),
        grad_cnt=kb.grad_cnt.at[flat].set(0.0),
        grad_sqnorm=kb.grad_sqnorm.at[flat].set(0.0),
        version=kb.version.at[flat].set(
            kb.version[flat] + upd.astype(jnp.int32)),
    )
    vals = dequantize_rows(codes_w, s_w, o_w)
    return (vals.reshape(*ids.shape, -1), kb,
            qscale.at[flat].set(s_w), qoffset.at[flat].set(o_w))


def kb_update_q(kb: KBState, qscale, qoffset, ids, values):
    """``kb_update`` for the quantized table: quantize the incoming rows and
    scatter codes + scale + offset. Returns (kb', qscale', qoffset')."""
    flat = ids.reshape(-1)
    vals = values.reshape(flat.shape[0], -1)
    codes, s, o = quantize_rows(vals)
    kb = kb._replace(
        table=kb.table.at[flat].set(codes),
        version=kb.version.at[flat].set(kb.version[flat] + 1),
        grad_sum=kb.grad_sum.at[flat].set(0.0),
        grad_cnt=kb.grad_cnt.at[flat].set(0.0),
        grad_sqnorm=kb.grad_sqnorm.at[flat].set(0.0),
        step=kb.step + 1,
    )
    return kb, qscale.at[flat].set(s), qoffset.at[flat].set(o)


def kb_flush_q(kb: KBState, qscale, qoffset, *, lazy_lr: float = 0.1,
               zmax: float = 3.0):
    """``kb_flush`` for the quantized table. Rows with an empty gradient
    cache keep their exact codes. Returns (kb', qscale', qoffset')."""
    rows = dequantize_rows(kb.table, qscale, qoffset)
    delta = pending_delta(kb.grad_sum, kb.grad_cnt, kb.grad_sqnorm,
                          lazy_lr=lazy_lr, zmax=zmax)
    codes_n, s_n, o_n = quantize_rows(rows + delta)
    upd = kb.grad_cnt > 0
    kb = kb._replace(
        table=jnp.where(upd[:, None], codes_n, kb.table),
        version=kb.version + upd.astype(jnp.int32),
        grad_sum=jnp.zeros_like(kb.grad_sum),
        grad_cnt=jnp.zeros_like(kb.grad_cnt),
        grad_sqnorm=jnp.zeros_like(kb.grad_sqnorm),
        step=kb.step + 1,
    )
    return (kb, jnp.where(upd, s_n, qscale), jnp.where(upd, o_n, qoffset))


def kb_nn_search_q(kb: KBState, qscale, qoffset, queries, k: int,
                   *, exclude_ids: Optional[jnp.ndarray] = None):
    """Exact-mode MIPS over the quantized bank (``quantized_scores``
    decomposition — no dequantized (N, D) matrix is ever materialized).
    Exact w.r.t. the quantized values; the engine's fp32 master re-rank
    restores exact final scores for rows with a master copy."""
    scores = quantized_scores(queries, kb.table, qscale, qoffset)
    if exclude_ids is not None:
        B = queries.shape[0]
        excl = jnp.zeros(scores.shape, bool).at[
            jnp.arange(B)[:, None], exclude_ids].set(
            exclude_ids >= 0, mode="drop")
        scores = jnp.where(excl, -jnp.inf, scores)
    return jax.lax.top_k(scores, k)


# ---------------------------------------------------------------------------
# feature-store ops
# ---------------------------------------------------------------------------

def fs_lookup_neighbors(fs: FeatureStore, ids: jnp.ndarray, k: int):
    """ids: (B,) -> (nbr_ids (B, k), nbr_weights (B, k))."""
    return fs.nbr_ids[ids, :k], fs.nbr_weights[ids, :k]


def fs_update_neighbors(fs: FeatureStore, ids, nbr_ids, nbr_weights):
    return fs._replace(nbr_ids=fs.nbr_ids.at[ids].set(nbr_ids),
                       nbr_weights=fs.nbr_weights.at[ids].set(nbr_weights))


def fs_update_labels(fs: FeatureStore, ids, labels, conf):
    """Confidence-gated label write (curriculum / label mining §4.2)."""
    better = conf > fs.label_conf[ids]
    return fs._replace(
        labels=fs.labels.at[ids].set(jnp.where(better, labels,
                                               fs.labels[ids])),
        label_conf=fs.label_conf.at[ids].set(jnp.where(better, conf,
                                                       fs.label_conf[ids])))
