"""RWKV6 WKV recurrence Pallas kernel.

The CUDA RWKV kernel keeps the (d, d) per-head state in registers and walks
time serially; the TPU adaptation keeps the state in VMEM scratch, walks
time with an in-kernel ``fori_loop``, and processes a whole (S, d) head
slice per grid step (grid = (B, H), both parallel). All operands for one
head (4 x S x d inputs + (d, d) state) fit comfortably in VMEM for
d = 64, S <= 8k; longer sequences chunk over an extra sequential grid axis
with the state carried in scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.env import resolve_interpret


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                seq_block: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, :, 0].astype(jnp.float32)     # (Sb, d)
    k = k_ref[0, :, 0].astype(jnp.float32)
    v = v_ref[0, :, 0].astype(jnp.float32)
    w = w_ref[0, :, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (d,)

    def step(t, carry):
        S_st, out = carry
        kv = k[t][:, None] * v[t][None, :]                   # (d, d)
        y = ((S_st + u[:, None] * kv) * r[t][:, None]).sum(0)
        S_st = S_st * w[t][:, None] + kv
        out = jax.lax.dynamic_update_slice(out, y[None], (t, 0))
        return S_st, out

    S0 = s_ref[...]
    out0 = jnp.zeros((seq_block, r.shape[1]), jnp.float32)
    S_fin, out = jax.lax.fori_loop(0, seq_block, step, (S0, out0))
    s_ref[...] = S_fin
    o_ref[0, :, 0] = out


def rwkv_wkv_pallas(r, k, v, w, u, *, seq_block: int = 512,
                    interpret: Optional[bool] = None):
    """r/k/v/w: (B, S, H, d); u: (H, d) -> (B, S, H, d) float32.
    ``interpret`` defaults to the process `KernelConfig` (repro.env)."""
    interpret = resolve_interpret(interpret)
    B, S, H, d = r.shape
    sb = min(seq_block, S)
    assert S % sb == 0, (S, sb)
    grid = (B, H, S // sb)
    return pl.pallas_call(
        functools.partial(_wkv_kernel, seq_block=sb),
        grid=grid,
        in_specs=[pl.BlockSpec((1, sb, 1, d), lambda b, h, s: (b, s, h, 0))
                  for _ in range(4)] + [
                  pl.BlockSpec((1, d), lambda b, h, s: (h, 0))],
        out_specs=pl.BlockSpec((1, sb, 1, d), lambda b, h, s: (b, s, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
