"""The bank's Pallas kernels compiled for a described TPU v5e, at the widths
``chip_smoke.py`` serves (a 2^20 x 128 bank, 512-id batches, an IVF index
of 1024 lists probed 32 at a time), plus one sharded lookup compiled for a
v5e 2x2 mesh.

Nothing runs. The TPU compiler is installed without a chip and refuses
what the chip would refuse: block shapes off the (8, 128) tiling, scoped
VMEM or SMEM overflow, primitives Mosaic cannot lower. Interpret-mode
tests see none of that. Every kernel program must contain its Mosaic call
(``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file. The persistent compilation cache is off around
these compiles, since a program compiled for an absent chip cannot be
read back.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import sharded_kb as skb
from repro.core.knowledge_bank import KBState
from repro.kernels import nn_search_ivf as ivf
from repro.kernels.kb_fused_lookup import (kb_fused_lookup_pallas,
                                           kb_fused_lookup_q_pallas)
from repro.kernels.kb_gather import kb_gather_pallas
from repro.kernels.lazy_apply import lazy_apply_pallas
from repro.kernels.nn_search import nn_search_pallas
from repro.sharding.partition import DistContext

N, D, B, K = 1 << 20, 128, 512, 10
NLIST, CAP, NPROBE = 1024, 1280, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases(batch):
    """name -> (fn, [(shape, dtype), ...]) for every bank-path kernel."""
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    ids = ((batch,), i32)
    q = ((batch, D), f32)
    occ = ((NLIST,), i32)
    return {
        "fused_lookup": (
            lambda t, g, c, s, i: kb_fused_lookup_pallas(
                t, g, c, s, i, interpret=False),
            [((N, D), f32), ((N, D), f32), ((N,), f32), ((N,), f32), ids]),
        "fused_lookup_q": (
            lambda t, sc, of, g, c, s, i: kb_fused_lookup_q_pallas(
                t, sc, of, g, c, s, i, interpret=False),
            [((N, D), i8), ((N,), f32), ((N,), f32), ((N, D), f32),
             ((N,), f32), ((N,), f32), ids]),
        "gather": (lambda t, i: kb_gather_pallas(t, i, interpret=False),
                   [((N, D), f32), ids]),
        "lazy_apply": (
            lambda t, g, c, s: lazy_apply_pallas(t, g, c, s,
                                                 interpret=False),
            [((N, D), f32), ((N, D), f32), ((N,), f32), ((N,), f32)]),
        "nn_search": (
            lambda qq, bank: nn_search_pallas(qq, bank, K, interpret=False),
            [q, ((N, D), f32)]),
        "ivf": (
            lambda t, c, pv, pi, o, qq: ivf.ivf_search_pallas(
                t, c, pv, pi, qq, K, NPROBE, bucket_occ=o, interpret=False),
            [((N, D), f32), ((NLIST, D), f32), ((NLIST * CAP, D), f32),
             ((NLIST * CAP,), i32), occ, q]),
        "ivf_q": (
            lambda t, qs, qo, c, pc, ps, po, pi, o, qq:
            ivf.ivf_search_quantized_pallas(
                t, qs, qo, c, pc, ps, po, pi, qq, K, NPROBE, bucket_occ=o,
                interpret=False),
            [((N, D), i8), ((N,), f32), ((N,), f32), ((NLIST, D), f32),
             ((NLIST * CAP, D), i8), ((NLIST * CAP,), f32),
             ((NLIST * CAP,), f32), ((NLIST * CAP,), i32), occ, q]),
        # one index over four shards of 256 lists: a batch whose chunk
        # schedule exceeds SMEM, so the kernel runs in query row groups
        "ivf_sharded": (
            lambda t, c, pv, pi, o, qq: ivf.ivf_search_sharded_pallas(
                t, c, pv, pi, qq, K, NPROBE, n_shards=4, bucket_occ=o,
                interpret=False),
            [((N, D), f32), ((NLIST, D), f32), ((NLIST * CAP, D), f32),
             ((NLIST * CAP,), i32), occ, q]),
    }


_CASES = [(name, B) for name in _kernel_cases(B)] + [
    ("fused_lookup", 8), ("fused_lookup", 4096), ("gather", 4096)]


@pytest.mark.parametrize("name,batch", _CASES,
                         ids=[f"{n}-B{b}" for n, b in _CASES])
def test_bank_kernel_compiles_for_v5e(one_chip, name, batch):
    fn, shapes = _kernel_cases(batch)[name]
    args = [_spec(one_chip, s, d) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_lookup_compiles_for_v5e_2x2(topo):
    """The sharded backend's lookup over a 2x2 mesh at 2^20 rows per chip:
    one all-reduce fan-in, and a quarter of the state on each chip."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    dist = DistContext(mesh=mesh)
    rows = 4 * N
    shapes = KBState(table=(rows, D), version=(rows,), grad_sum=(rows, D),
                     grad_cnt=(rows,), grad_sqnorm=(rows,),
                     norm_ema=(rows,), step=())
    dtypes = KBState(*([jnp.float32, jnp.int32] + [jnp.float32] * 4
                       + [jnp.int32]))
    state = KBState(*[_spec(NamedSharding(mesh, p), s, d) for s, d, p in
                      zip(shapes, dtypes, skb.kb_pspecs(dist))])
    ids = _spec(NamedSharding(mesh, P()), (B,), jnp.int32)
    compiled = jax.jit(lambda st, i: skb.sharded_kb_lookup(
        st, i, dist)).lower(state, ids).compile()
    assert "all-reduce" in compiled.as_text()
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    whole = rows * (8 * D + 4 * 4) + B * 4
    assert abs(per_chip / whole - 0.25) < 0.01, per_chip / whole
