"""Blocked top-k MIPS Pallas kernel — the ScaNN-shard adapted to TPU (§3.2).

Design (DESIGN.md §3 item 3): instead of ScaNN's CPU-side anisotropic
quantization, a TPU shard scores its rows *densely* on the MXU in
(QB x NB) VMEM tiles and maintains a running top-k per query in VMEM
scratch. The k best are extracted with k iterative max+mask passes (k is
small and static), which lowers to pure VPU ops — no sort, no top_k
primitive needed inside the kernel.

Grid: (num_query_blocks, num_bank_blocks); the bank axis is the sequential
("arbitrary") dimension so the running top-k scratch carries across it.
VMEM per step: QB*D + NB*D + QB*NB + 2*QB*k floats — sized so QB=NB=256,
D<=1024 stays well under 16 MB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.env import fit_block_rows, resolve_interpret

NEG = -1e30
# the bank is an fp32 store: every score and one-hot-gather matmul runs at
# full fp32 precision (the TPU default rounds f32 operands through bf16)
HIGHEST = jax.lax.Precision.HIGHEST


def overfetch_exclude_topk(search, n_rows: int, k: int, exclude_ids):
    """Shared exclusion semantics for every top-k search path: over-fetch
    ``k + E`` candidates via ``search(kk) -> (scores, ids)``, mask
    excluded ids post-merge (-1 entries in ``exclude_ids`` are inert),
    re-top-k. At most E of the k+E candidates can be excluded per query,
    so whenever the candidate pool holds k survivors the result equals
    the dense pre-mask semantics. One definition — ShardedBackend exact,
    the sharded-IVF op, and the meshless reference all call it — so the
    three backends cannot silently diverge."""
    E = exclude_ids.shape[1]
    kk = min(k + E, n_rows)
    s, i = search(kk)
    excl = ((i[:, :, None] == exclude_ids[:, None, :]) &
            (exclude_ids >= 0)[:, None, :]).any(-1)
    s = jnp.where(excl, -jnp.inf, s)
    s2, sel = jax.lax.top_k(s, k)
    return s2, jnp.take_along_axis(i, sel, axis=1)


def _merge_topk(scores, ids, best_s, best_i, k: int):
    """scores/ids: (QB, M) candidates; best_s/best_i: (QB, k) running.
    Returns updated (best_s, best_i). Ties prefer lower id (stable).

    Equivalent to k rounds of argmax over ``concat([best, candidates])``
    with the lowest-id tie-break, masking the FIRST selected column each
    round. The two halves stay separate arrays (the running top-k comes
    first in that column order) and every step is an elementwise op or a
    lane reduction, which is what the TPU kernel lowering supports."""
    big = jnp.iinfo(jnp.int32).max
    col_b = jax.lax.broadcasted_iota(jnp.int32, best_s.shape, 1)
    col_c = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    out_s = jnp.full(best_s.shape, NEG, jnp.float32)
    out_i = jnp.full(best_i.shape, big, jnp.int32)
    for t in range(k):
        # argmax with lowest-id tie-break: order by (score, -id)
        m = jnp.maximum(jnp.max(best_s, axis=1, keepdims=True),
                        jnp.max(scores, axis=1, keepdims=True))
        max_b = best_s >= m
        max_c = scores >= m
        sel_id = jnp.minimum(
            jnp.min(jnp.where(max_b, best_i, big), axis=1, keepdims=True),
            jnp.min(jnp.where(max_c, ids, big), axis=1, keepdims=True))
        sel_b = max_b & (best_i == sel_id)
        sel_c = max_c & (ids == sel_id)
        # mask only the first selected column: a running-top-k column
        # precedes every candidate column
        first_b = jnp.min(jnp.where(sel_b, col_b, big), axis=1,
                          keepdims=True)
        first_c = jnp.min(jnp.where(sel_c, col_c, big), axis=1,
                          keepdims=True)
        take_b = sel_b & (col_b == first_b)
        take_c = sel_c & (col_c == first_c) & (first_b == big)
        out_s = jnp.where(col_b == t, m, out_s)
        out_i = jnp.where(col_b == t, sel_id, out_i)
        best_s = jnp.where(take_b, NEG, best_s)
        scores = jnp.where(take_c, NEG, scores)
    return out_s, out_i


def _nn_kernel(q_ref, bank_ref, os_ref, oi_ref, bs_ref, bi_ref, *, k: int,
               nb_block: int, n_total: int):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _():
        bs_ref[...] = jnp.full_like(bs_ref, NEG)
        bi_ref[...] = jnp.full_like(bi_ref, jnp.iinfo(jnp.int32).max)

    q = q_ref[...].astype(jnp.float32)                    # (QB, D)
    b = bank_ref[...].astype(jnp.float32)                 # (NB, D)
    scores = jax.lax.dot_general(q, b, (((1,), (1,)), ((), ())),
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
    base = nb * nb_block
    ids = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    # mask padding rows beyond the true bank size
    scores = jnp.where(ids < n_total, scores, NEG)
    bs, bi = _merge_topk(scores, ids, bs_ref[...], bi_ref[...], k)
    bs_ref[...] = bs
    bi_ref[...] = bi

    @pl.when(nb == pl.num_programs(1) - 1)
    def _():
        os_ref[...] = bs_ref[...]
        oi_ref[...] = bi_ref[...]


def nn_search_pallas(queries, bank, k: int, *, q_block: int = 128,
                     n_block: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """queries: (B, D); bank: (N, D) -> (scores (B, k), ids (B, k)).

    ``interpret``/``n_block`` default to the process `KernelConfig`
    (repro.env); the bank tile is VMEM-fitted against the budget."""
    interpret = resolve_interpret(interpret)
    B, D = queries.shape
    N = bank.shape[0]
    if n_block is None:
        n_block = fit_block_rows(D, n_arrays=2)
    qb = min(q_block, B)
    nb = min(n_block, N)
    # pad to block multiples
    Bp = -(-B // qb) * qb
    Np = -(-N // nb) * nb
    qp = jnp.pad(queries, ((0, Bp - B), (0, 0)))
    bp = jnp.pad(bank, ((0, Np - N), (0, 0)))
    grid = (Bp // qb, Np // nb)
    kern = functools.partial(_nn_kernel, k=k, nb_block=nb, n_total=N)
    out_s, out_i = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((qb, D), lambda i, j: (i, 0)),
                  pl.BlockSpec((nb, D), lambda i, j: (j, 0))],
        out_specs=[pl.BlockSpec((qb, k), lambda i, j: (i, 0)),
                   pl.BlockSpec((qb, k), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bp, k), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, k), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((qb, k), jnp.float32),
                        pltpu.VMEM((qb, k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qp, bp)
    return out_s[:B], out_i[:B]
