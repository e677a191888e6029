"""Causal / sliding-window flash attention Pallas kernel.

TPU adaptation notes:
- block-triangular grid: KV blocks strictly above the causal diagonal are
  skipped with ``pl.when`` — this removes the 2x FLOP overhead the pure-XLA
  blockwise path pays (layers.flash_attention_jax), see EXPERIMENTS §Perf.
- online softmax state (m, l, acc) lives in VMEM scratch across the KV grid
  dimension; block sizes default to (128, 128) so q/k/v tiles + scores fit
  VMEM with MXU-aligned matmul dims.
- sliding-window masking folds into the same block mask; fully-outside
  blocks are skipped entirely (this is what makes the long_500k window
  serve variant linear instead of quadratic).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.env import resolve_interpret

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  q_block: int, kv_block: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0 = qi * q_block
    k0 = ki * kv_block
    # block-triangular skip: no FLOPs for blocks fully outside the mask
    pred = jnp.bool_(True)
    if causal:
        pred &= k0 <= q0 + q_block - 1     # block not above the diagonal
    if window:
        pred &= q0 - (k0 + kv_block - 1) < window  # block not out the window

    @pl.when(pred)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale            # (QB, d)
        k = k_ref[0].astype(jnp.float32)                    # (KB, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, bool)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, q_block: int = 128,
                           kv_block: int = 128,
                           interpret: Optional[bool] = None):
    """q/k/v: (BH, S, d) with heads flattened into the batch dim.
    Returns (BH, S, d). ``interpret`` defaults to the process
    `KernelConfig` (repro.env)."""
    interpret = resolve_interpret(interpret)
    BH, S, d = q.shape
    qb = min(q_block, S)
    kb = min(kv_block, S)
    assert S % qb == 0 and S % kb == 0, (S, qb, kb)
    grid = (BH, S // qb, S // kb)
    kern = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), causal=causal,
        window=window, softcap=softcap, q_block=qb, kv_block=kb)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((1, qb, d), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, kb, d), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, kb, d), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, qb, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((qb, 1), jnp.float32),
                        pltpu.VMEM((qb, 1), jnp.float32),
                        pltpu.VMEM((qb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
