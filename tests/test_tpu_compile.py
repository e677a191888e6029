"""The bank's Pallas kernels compiled for a described TPU v5e, at the widths
``chip_smoke.py`` serves (a 2^20 x 128 bank, 512-id batches, an IVF index
of 1024 lists probed 32 at a time), the engine's fp32 lookup, lazy_grad
and update programs and its int8 lazy_grad and update at the batch sizes
the server coalesces, plus the sharded lookup and lazy_grad compiled for a
v5e 2x2 mesh.

The engine's programs donate the bank state: each must alias every state
leaf to its output and hold no copy of a state-sized array, which would
move the whole bank on every call.

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
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import sharded_kb as skb
from repro.core.kb_engine import KBEngine, ShardedBackend
from repro.core.knowledge_bank import KBState
from repro.kernels import nn_search_ivf as ivf
from repro.kernels.kb_fused_lookup import kb_fused_lookup_q_pallas
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


def _kb_state(rows, shardings, table_dtype=jnp.float32):
    """A ``KBState`` of ``rows`` x D shapes, one sharding per leaf."""
    dtypes = KBState(*([table_dtype, jnp.int32] + [jnp.float32] * 4
                       + [jnp.int32]))
    shapes = KBState(table=(rows, D), version=(rows,), grad_sum=(rows, D),
                     grad_cnt=(rows,), grad_sqnorm=(rows,),
                     norm_ema=(rows,), step=())
    return KBState(*[_spec(sh, s, d)
                     for sh, s, d in zip(shardings, shapes, dtypes)])


def _op_args(eng, op, state, sharding, batch, qscale=()):
    """The engine's jitted ``op`` and its arguments as the engine passes
    them: the state first (an int8 engine's lookup and update take its
    ``qscale`` and ``qoffset`` after it), then the padded batch."""
    ids = _spec(sharding, (batch,), jnp.int32)
    rows = _spec(sharding, (batch, D))
    return {"lookup": (eng._lookup_fn, (state, *qscale, ids)),
            "lazy_grad": (eng._lazy_fn,
                          (state, ids, rows, _spec(sharding, (batch,)))),
            "update": (eng._update_fn, (state, *qscale, ids, rows))}[op]


def _assert_state_donated(compiled, n_leaves=len(KBState._fields)):
    """Every state leaf (the program's first parameters) is aliased to an
    output, and no copy of a per-device state-sized array (N rows, with
    or without the D columns) is left in the program."""
    text = compiled.as_text()
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert alias, "no input_output_alias: the state is not donated"
    aliased = {int(p) for p in re.findall(r"\((\d+), \{\}", alias[1])}
    assert set(range(n_leaves)) <= aliased, sorted(aliased)
    copies = re.findall(rf"= \w+\[{N}(?:,{D})?\]\{{[^}}]*\}} copy\(", text)
    assert not copies, copies


def _kernel_cases(batch):
    """name -> (fn, [(shape, dtype), ...]) for every bank-path kernel."""
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    ids = ((batch,), i32)
    q = ((batch, D), f32)
    occ = ((NLIST,), i32)
    return {
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


_CASES = [(name, B) for name in _kernel_cases(B)] + [("gather", 4096)]


@pytest.mark.parametrize("name,batch", _CASES,
                         ids=[f"{n}-B{b}" for n, b in _CASES])
def test_bank_kernel_compiles_for_v5e(one_chip, name, batch):
    fn, shapes = _kernel_cases(batch)[name]
    args = [_spec(one_chip, s, d) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [8, 512, 2048, 4096])
def test_fp32_lookup_compiles_for_v5e(one_chip, batch):
    """The engine's fp32 lookup program on the Pallas backend gathers and
    scatters its rows by id: its FLOPs follow the batch (a one-hot gather
    over the bank does 2·N·B·D, 5.5e11 at 2,048 ids), and no (N, 1)
    column, which the chip pads to 128 lanes, appears anywhere in it."""
    eng = KBEngine(8, D, backend="pallas", interpret=False)
    state = _kb_state(N, [one_chip] * len(KBState._fields))
    ids = _spec(one_chip, (batch,), jnp.int32)
    compiled = eng._lookup_fn.lower(state, ids).compile()
    assert compiled.cost_analysis()["flops"] < 1e9
    assert f"f32[{N},1]" not in compiled.as_text()
    _assert_state_donated(compiled)


@pytest.mark.parametrize("storage,op,batch", [
    (st, op, b) for st in ("fp32", "int8") for op in ("lazy_grad", "update")
    for b in (8, 2048)])
def test_writes_donate_the_state_for_v5e(one_chip, storage, op, batch):
    """The engine's lazy_grad and update programs scatter their rows into
    the donated state in place: O(B·D) work, no copy of the bank. On int8
    storage the donated state is the int8 table, its fp32 caches and, for
    update, the per-row scale and offset. The int8 lookup is not checked:
    its fused Pallas kernel declares no ``input_output_aliases``, so XLA
    aliases the donated state but still copies ``table`` and ``grad_sum``
    into the kernel's outputs."""
    eng = KBEngine(8, D, backend="pallas", storage=storage, interpret=False)
    int8 = storage == "int8"
    state = _kb_state(N, [one_chip] * len(KBState._fields),
                      jnp.int8 if int8 else jnp.float32)
    qscale = (_spec(one_chip, (N,)), _spec(one_chip, (N,))) if int8 else ()
    fn, args = _op_args(eng, op, state, one_chip, batch, qscale)
    compiled = fn.lower(*args).compile()
    assert compiled.cost_analysis()["flops"] < 1e9
    _assert_state_donated(compiled, len(KBState._fields)
                          + (len(qscale) if op == "update" else 0))


def test_sharded_lookup_compiles_for_v5e_2x2(topo):
    """The sharded backend's lookup over a 2x2 mesh at 2^20 rows per chip:
    one all-reduce fan-in, and a quarter of the state on each chip."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    dist = DistContext(mesh=mesh)
    rows = 4 * N
    state = _kb_state(rows, [NamedSharding(mesh, p)
                             for p in skb.kb_pspecs(dist)])
    ids = _spec(NamedSharding(mesh, P()), (B,), jnp.int32)
    compiled = jax.jit(lambda st, i: skb.sharded_kb_lookup(
        st, i, dist)).lower(state, ids).compile()
    assert "all-reduce" in compiled.as_text()
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    whole = rows * (8 * D + 4 * 4) + B * 4
    assert abs(per_chip / whole - 0.25) < 0.01, per_chip / whole


@pytest.mark.parametrize("op,batch", [(op, b) for op in ("lookup",
                                                         "lazy_grad")
                                      for b in (8, 2048)])
def test_sharded_engine_ops_donate_for_v5e_2x2(topo, monkeypatch, op,
                                               batch):
    """The sharded engine's own lookup and lazy_grad programs over a 2x2
    mesh at 2^22 rows alias every state shard, the (N,) leaves as well as
    the (N, D) ones, and copy none of them."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    bk = ShardedBackend(DistContext(mesh=mesh))
    state = _kb_state(4 * N, [NamedSharding(mesh, p)
                              for p in skb.kb_pspecs(bk.dist)])
    # a described chip holds no array: the engine gets the state's shapes
    monkeypatch.setattr(bk, "create", lambda *a, **kw: state)
    eng = KBEngine(4 * N, D, backend=bk)
    fn, args = _op_args(eng, op, state, NamedSharding(mesh, P()), batch)
    _assert_state_donated(fn.lower(*args).compile())
