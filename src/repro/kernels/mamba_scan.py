"""Chunked Mamba selective-scan Pallas kernel.

The portable ``lax.scan`` path (repro.models.ssm) writes the (B, di, ds)
state to HBM every step — the dominant HBM term for jamba training
(EXPERIMENTS §Roofline). The kernel keeps the state tile in VMEM across an
in-kernel time loop:

grid = (B, di/di_block, S/seq_block), time sequential in the last axis with
the (di_block, ds) state carried in VMEM scratch; per grid step it streams
only the (seq_block, di_block) input tiles. HBM traffic drops from
O(S * di * ds) to O(S * di) — a factor of ds (= 16).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.env import resolve_interpret


def _mamba_kernel(delta_ref, bm_ref, cm_ref, x_ref, a_ref, o_ref, h_ref, *,
                  seq_block: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    delta = delta_ref[0].astype(jnp.float32)        # (Sb, db)
    bm = bm_ref[0].astype(jnp.float32)              # (Sb, ds)
    cm = cm_ref[0].astype(jnp.float32)              # (Sb, ds)
    x = x_ref[0].astype(jnp.float32)                # (Sb, db)
    A = a_ref[...].astype(jnp.float32)              # (db, ds)

    def step(t, carry):
        h, out = carry                              # h: (db, ds)
        a_t = jnp.exp(delta[t][:, None] * A)
        h = a_t * h + (delta[t] * x[t])[:, None] * bm[t][None, :]
        y_t = jnp.sum(h * cm[t][None, :], axis=-1)  # (db,)
        out = jax.lax.dynamic_update_slice(out, y_t[None], (t, 0))
        return h, out

    out0 = jnp.zeros((seq_block, delta.shape[1]), jnp.float32)
    h_fin, out = jax.lax.fori_loop(0, seq_block, step, (h_ref[...], out0))
    h_ref[...] = h_fin
    o_ref[0] = out


def mamba_scan_pallas(delta, bm, cm, x, A, *, di_block: int = 512,
                      seq_block: int = 256,
                      interpret: Optional[bool] = None):
    """delta/x: (B, S, di); bm/cm: (B, S, ds); A: (di, ds).
    Returns y: (B, S, di) f32 (the SSM output before D-skip/gating).
    ``interpret`` defaults to the process `KernelConfig` (repro.env)."""
    interpret = resolve_interpret(interpret)
    B, S, di = delta.shape
    ds = bm.shape[-1]
    db = min(di_block, di)
    sb = min(seq_block, S)
    assert di % db == 0 and S % sb == 0, (di, db, S, sb)
    grid = (B, di // db, S // sb)
    return pl.pallas_call(
        functools.partial(_mamba_kernel, seq_block=sb),
        grid=grid,
        in_specs=[pl.BlockSpec((1, sb, db), lambda b, d, s: (b, s, d)),
                  pl.BlockSpec((1, sb, ds), lambda b, d, s: (b, s, 0)),
                  pl.BlockSpec((1, sb, ds), lambda b, d, s: (b, s, 0)),
                  pl.BlockSpec((1, sb, db), lambda b, d, s: (b, s, d)),
                  pl.BlockSpec((db, ds), lambda b, d, s: (d, 0))],
        out_specs=pl.BlockSpec((1, sb, db), lambda b, d, s: (b, s, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((db, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(delta, bm, cm, x, A)
