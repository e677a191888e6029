"""Fused lazy-update application kernel — the Knowledge Bank's §3.2 op as a
single pass: for a block of rows, compute the cached-gradient average, apply
outlier clipping, update the table rows, and emit cleared caches.

On a TPU KB shard this is the serving hot path ("apply pending on next
lookup"): one HBM read of (rows, grad_sum) + one write of (rows', zeros)
instead of the 6 separate gather/scatter ops the unfused jnp path performs.
Grid: row blocks (fully parallel); everything fits a VMEM tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.env import fit_block_rows, resolve_interpret


def _lazy_apply_kernel(tbl_ref, gsum_ref, gcnt_ref, gsq_ref,
                       out_tbl_ref, out_gsum_ref, out_gcnt_ref, out_gsq_ref,
                       *, lazy_lr: float, zmax: float):
    tbl = tbl_ref[...].astype(jnp.float32)          # (R, D)
    gsum = gsum_ref[...]
    gcnt = gcnt_ref[...]                            # (R, 1)
    gsq = gsq_ref[...]
    cnt = jnp.maximum(gcnt, 1.0)
    avg = gsum / cnt
    avg_norm = jnp.sqrt(jnp.maximum(jnp.sum(avg * avg, -1, keepdims=True),
                                    1e-24))
    rms = jnp.sqrt(gsq / cnt)
    cap = zmax * jnp.maximum(rms, 1e-12)
    scale = jnp.minimum(1.0, cap / avg_norm)
    delta = -lazy_lr * avg * scale
    new = jnp.where(gcnt > 0, tbl + delta, tbl)
    out_tbl_ref[...] = new.astype(out_tbl_ref.dtype)
    out_gsum_ref[...] = jnp.zeros_like(gsum)
    out_gcnt_ref[...] = jnp.zeros_like(gcnt)
    out_gsq_ref[...] = jnp.zeros_like(gsq)


def lazy_apply_pallas(table, grad_sum, grad_cnt, grad_sqnorm, *,
                      lazy_lr: float = 0.1, zmax: float = 3.0,
                      row_block: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """table: (N, D); grad_sum: (N, D) f32; grad_cnt/grad_sqnorm: (N,) f32.
    Returns (new_table, zeroed grad_sum/cnt/sqnorm) — kb_flush semantics.
    ``interpret``/``row_block`` default to the process `KernelConfig`
    (repro.env); the row tile is VMEM-fitted (4 in + 4 out streams)."""
    interpret = resolve_interpret(interpret)
    N, D = table.shape
    if row_block is None:
        row_block = fit_block_rows(D, n_arrays=8)
    rb = min(row_block, N)
    Np = -(-N // rb) * rb
    pad = lambda a: jnp.pad(a, ((0, Np - N),) + ((0, 0),) * (a.ndim - 1))
    cnt2 = grad_cnt[:, None]
    sq2 = grad_sqnorm[:, None]
    kern = functools.partial(_lazy_apply_kernel, lazy_lr=lazy_lr, zmax=zmax)
    out = pl.pallas_call(
        kern,
        grid=(Np // rb,),
        in_specs=[pl.BlockSpec((rb, D), lambda i: (i, 0)),
                  pl.BlockSpec((rb, D), lambda i: (i, 0)),
                  pl.BlockSpec((rb, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rb, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rb, D), lambda i: (i, 0)),
                   pl.BlockSpec((rb, D), lambda i: (i, 0)),
                   pl.BlockSpec((rb, 1), lambda i: (i, 0)),
                   pl.BlockSpec((rb, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Np, D), table.dtype),
                   jax.ShapeDtypeStruct((Np, D), jnp.float32),
                   jax.ShapeDtypeStruct((Np, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Np, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pad(table), pad(grad_sum), pad(cnt2), pad(sq2))
    new_tbl, gsum, gcnt, gsq = out
    return (new_tbl[:N], gsum[:N], gcnt[:N, 0], gsq[:N, 0])
