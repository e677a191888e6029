"""Fused int8 KB lookup kernel: dequant + lazy-apply + requant +
cache-clear in ONE pass over the bank.

Only the int8-coded bank (``KBEngine(storage="int8")`` on the Pallas
backend) runs this kernel. The fp32 lookup gathers and scatters its B rows
by id instead (``repro.core.kb_engine.PallasBackend``): the grid here runs
over every bank tile, so each call streams all N rows and their caches
and gathers by a (tile rows, B) one-hot product — N·D bytes and 2·N·B·D
FLOPs a call where a by-id gather needs B·D. Moving this kernel to a by-id
gather as well waits for a benchmark cell that runs the int8 bank.

Per bank tile the kernel:

1. builds the (tile rows, B) one-hot membership of the requested ids,
2. dequantizes the tile and computes the outlier-clipped cached-gradient
   average (``pending_delta`` semantics, same formula as
   ``repro.core.knowledge_bank``),
3. re-quantizes the rows that changed and writes back the codes, the
   per-row (scale, offset) and the zeroed caches of touched rows,
4. accumulates ``onehot^T @ dequantized_tile`` on the MXU into the (B, D)
   output.

Grid: bank tiles, sequential; the (B, D) result block stays resident in
VMEM across the grid and accumulates every tile's contribution. Version
counters are (N,) int32 metadata — the caller bumps them with a jnp
scatter (see ``repro.core.kb_engine.KBEngine``).

ids are padded with -1 (matches no row). Duplicate ids are deterministic:
every occurrence reads the same updated row.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.env import fused_lookup_block, resolve_interpret
from repro.kernels.nn_search import HIGHEST


def _membership(idr_ref, j, n_block: int):
    """The tile's (NB, B) one-hot of the padded ids (a (1, B) row) and its
    (NB, 1) per-row ``touched`` flag, reduced over lanes — reshaping a
    (NB,) bool reduction into a column is not lowerable on TPU."""
    ids = idr_ref[...]                                      # (1, B)
    rows = j * n_block + jax.lax.broadcasted_iota(
        jnp.int32, (n_block, ids.shape[1]), 0)
    hits = (ids == rows).astype(jnp.float32)                # (NB, B)
    touched = jnp.max(hits, axis=1, keepdims=True) > 0      # (NB, 1)
    return hits, touched


# ids per gather matmul: bounds the (chunk, D) product the MXU writes
# before it is added into the resident output block
GATHER_CHUNK = 512


def _accumulate_rows(o_ref, hits, rows):
    """o_ref (B, D) += hits^T @ rows on the MXU, GATHER_CHUNK ids at a time
    and at full fp32 precision: a one-hot gather must return the stored
    rows bit-for-bit."""
    B = o_ref.shape[0]
    for lo in range(0, B, GATHER_CHUNK):
        n = min(GATHER_CHUNK, B - lo)
        o_ref[lo:lo + n, :] += jax.lax.dot_general(
            hits[:, lo:lo + n], rows, (((0,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)


def _fused_kernel_q(idr_ref, tbl_ref, scl_ref, off_ref, gsum_ref, gcnt_ref,
                    gsq_ref, o_tbl_ref, o_scl_ref, o_off_ref, o_gsum_ref,
                    o_gcnt_ref, o_gsq_ref, o_vals_ref, *,
                    n_block: int, lazy_lr: float, zmax: float):
    """The fused lookup over an int8-coded bank: dequantize the tile in
    VMEM, apply the clipped cached-gradient average, RE-quantize the rows
    that changed, and accumulate the dequantization of what was written —
    ``kb_lookup_q`` semantics (repro.core.knowledge_bank), one HBM pass.
    Rows without pending gradients keep their exact codes/scale/offset, so
    a read-only lookup is bit-stable (no re-quantization drift)."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        o_vals_ref[...] = jnp.zeros_like(o_vals_ref)

    hits, touched = _membership(idr_ref, j, n_block)

    codes = tbl_ref[...].astype(jnp.float32)                # (NB, D)
    scl = scl_ref[...]                                      # (NB, 1)
    off = off_ref[...]
    tbl = codes * scl + off                                 # fused dequant
    gsum = gsum_ref[...]
    gcnt = gcnt_ref[...]                                    # (NB, 1)
    gsq = gsq_ref[...]

    # pending_delta, verbatim semantics of the dense reference
    cnt = jnp.maximum(gcnt, 1.0)
    avg = gsum / cnt
    avg_norm = jnp.sqrt(jnp.sum(avg * avg, -1, keepdims=True))
    rms = jnp.sqrt(gsq / cnt)
    cap = zmax * jnp.maximum(rms, 1e-12)
    scale = jnp.minimum(1.0, cap / jnp.maximum(avg_norm, 1e-12))
    apply = touched & (gcnt > 0)
    new_tbl = tbl - lazy_lr * avg * scale

    # re-quantize ONLY the applied rows (quantize_rows semantics)
    hi = jnp.max(new_tbl, -1, keepdims=True)
    lo = jnp.min(new_tbl, -1, keepdims=True)
    off_n = 0.5 * (hi + lo)
    scl_n = (hi - lo) / 254.0
    scl_n = jnp.where(scl_n > 0, scl_n, 1.0)
    codes_n = jnp.clip(jnp.round((new_tbl - off_n) / scl_n), -127, 127)

    codes_w = jnp.where(apply, codes_n, codes)
    scl_w = jnp.where(apply, scl_n, scl)
    off_w = jnp.where(apply, off_n, off)
    o_tbl_ref[...] = codes_w.astype(o_tbl_ref.dtype)
    o_scl_ref[...] = scl_w
    o_off_ref[...] = off_w
    o_gsum_ref[...] = jnp.where(touched, 0.0, gsum)
    o_gcnt_ref[...] = jnp.where(touched, 0.0, gcnt)
    o_gsq_ref[...] = jnp.where(touched, 0.0, gsq)
    _accumulate_rows(o_vals_ref, hits, codes_w * scl_w + off_w)


def kb_fused_lookup_q_pallas(table, qscale, qoffset, grad_sum, grad_cnt,
                             grad_sqnorm, ids, *, lazy_lr: float = 0.1,
                             zmax: float = 3.0,
                             n_block: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Quantized fused lookup. table: (N, D) int8 codes; qscale/qoffset:
    (N,) f32 per-row affine; grad_sum: (N, D) f32; grad_cnt/grad_sqnorm:
    (N,) f32; ids: (B,) int32.

    Returns (vals (B, D) f32, new_table int8, new_qscale, new_qoffset,
    new_grad_sum, new_grad_cnt, new_grad_sqnorm) — ``kb_lookup_q``
    semantics except the version counter (bumped by the caller).
    ``interpret``/``n_block`` default to the process `KernelConfig`
    (repro.env); the bank tile shrinks with the batch so the (n_block, B)
    one-hot and the resident (B, D) output stay inside the VMEM budget."""
    interpret = resolve_interpret(interpret)
    N, D = table.shape
    B = ids.shape[0]
    if n_block is None:
        n_block = fused_lookup_block(B, D)
    nb = min(n_block, N)
    Bp = -(-B // 8) * 8
    Np = -(-N // nb) * nb
    idp = jnp.pad(ids.astype(jnp.int32), (0, Bp - B), constant_values=-1)
    pad = lambda a: jnp.pad(a, ((0, Np - N),) + ((0, 0),) * (a.ndim - 1))
    # padded rows must keep scale 1 (scale 0 would poison the requant guard)
    sclp = jnp.pad(qscale[:, None], ((0, Np - N), (0, 0)),
                   constant_values=1.0)
    kern = functools.partial(_fused_kernel_q, n_block=nb, lazy_lr=lazy_lr,
                             zmax=zmax)
    row2 = pl.BlockSpec((nb, D), lambda j: (j, 0))
    col2 = pl.BlockSpec((nb, 1), lambda j: (j, 0))
    out = pl.pallas_call(
        kern,
        grid=(Np // nb,),
        in_specs=[pl.BlockSpec((1, Bp), lambda j: (0, 0)),
                  row2, col2, col2, row2, col2, col2],
        out_specs=[row2, col2, col2, row2, col2, col2,
                   pl.BlockSpec((Bp, D), lambda j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((Np, D), table.dtype),
                   jax.ShapeDtypeStruct((Np, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Np, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Np, D), jnp.float32),
                   jax.ShapeDtypeStruct((Np, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Np, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kb_fused_lookup_q",
    )(idp[None, :], pad(table), sclp, pad(qoffset[:, None]), pad(grad_sum),
      pad(grad_cnt[:, None]), pad(grad_sqnorm[:, None]))
    new_tbl, scl, off, gsum, gcnt, gsq, vals = out
    return (vals[:B], new_tbl[:N], scl[:N, 0], off[:N, 0], gsum[:N],
            gcnt[:N, 0], gsq[:N, 0])
