"""Knowledge-bank gather kernel: embedding lookup as blocked one-hot MXU
matmul — the DynamicEmbedding lookup adapted to TPU.

Random-row gathers from HBM are slow on TPU (no hardware gather); for the
lookup batch sizes CARLS serves per step (B*K of order 1e3-1e4) against a
bank shard in VMEM-sized tiles, computing ``onehot(ids) @ bank_tile`` on the
MXU and accumulating across tiles is bandwidth-optimal: every bank tile is
streamed HBM->VMEM exactly once, and the one-hot matmul is free relative to
the stream. Grid: (id blocks, bank tiles); accumulator in VMEM scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.env import fit_block_rows, kernel_config, resolve_interpret
from repro.kernels.nn_search import HIGHEST


def _gather_kernel(ids_ref, bank_ref, o_ref, acc_ref, *, n_block: int):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = ids_ref[...]                                     # (IB, 1)
    base = nb * n_block
    rows = base + jax.lax.broadcasted_iota(
        jnp.int32, (ids.shape[0], n_block), 1)
    onehot = (ids == rows).astype(jnp.float32)             # (IB, NB)
    bank = bank_ref[...].astype(jnp.float32)               # (NB, D)
    acc_ref[...] += jax.lax.dot_general(
        onehot, bank, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(nb == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def kb_gather_pallas(table, ids, *, id_block: int = 256,
                     n_block: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """table: (N, D); ids: (B,) int32 -> (B, D). ``interpret``/``n_block``
    default to the process `KernelConfig` (repro.env); the bank tile is
    fitted so the (id_block, n_block) one-hot stays inside the VMEM
    budget."""
    interpret = resolve_interpret(interpret)
    N, D = table.shape
    B = ids.shape[0]
    ib = min(id_block, -(-B // 8) * 8)
    if n_block is None:
        # one-hot is (ib, nb): charge ib floats per bank row on top of the
        # streamed (nb, D) tile
        n_block = fit_block_rows(D + ib, want=kernel_config().block_ids,
                                 n_arrays=2, fixed_bytes=ib * D * 4)
    nb = min(n_block, N)
    Bp = -(-B // ib) * ib
    Np = -(-N // nb) * nb
    idp = jnp.pad(ids, (0, Bp - B), constant_values=-1)
    tp = jnp.pad(table, ((0, Np - N), (0, 0)))
    grid = (Bp // ib, Np // nb)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, n_block=nb),
        grid=grid,
        in_specs=[pl.BlockSpec((ib, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((nb, D), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((ib, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, D), table.dtype),
        scratch_shapes=[pltpu.VMEM((ib, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idp[:, None], tp)
    return out[:B]
