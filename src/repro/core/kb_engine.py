"""Pluggable Knowledge-Bank engine: one semantics, three execution backends.

The paper's Knowledge Bank (§3.2) is a service contract — lookup / update /
lazy_grad / flush / nn_search over shared state — not an implementation.
This module makes that contract explicit:

- ``KBBackend``   : the protocol. Pure functions over the shared ``KBState``
                    from ``repro.core.knowledge_bank``.
- ``DenseBackend``: the jnp reference ops (semantics ground truth).
- ``ShardedBackend``: the mesh-sharded shard_map ops from
                    ``repro.core.sharded_kb`` (owner-masked scatters, psum
                    fan-in) — same math, distributed state.
- ``PallasBackend``: the TPU serving path. ``flush`` runs the fused
                    ``lazy_apply`` kernel, ``nn_search`` the blocked MIPS
                    kernel. ``lookup`` and the writes (update / lazy_grad)
                    gather and scatter only the requested rows by id (the
                    jnp path): their work is O(B·D), not O(N·D).

Backends are interchangeable bit-for-bit (tests/test_kb_engine.py drives
the same op sequence through all three and compares every state leaf).

Two client surfaces sit on top of the backend protocol:

- ``KBOps`` (``make_kb_ops``): the IN-GRAPH functional facade — pure
  closures over a backend chosen once, traceable inside jitted trainer
  steps and maker programs. This is how the left two corners of the CARLS
  triangle (trainers, knowledge makers) reach the bank without a single
  per-callsite mesh branch.
- ``KBEngine``: the stateful HOST shell the async server talks to.

``KBEngine`` is the stateful shell the host runtime talks to: it owns a
``KBState``, jits each backend op once, and pads every batch to power-of-two
jit buckets so arbitrary (and coalesced — see ``repro.core.async_runtime``)
request sizes hit a bounded set of compiled programs. Padding is free by
construction: lookups/updates pad with a duplicated real entry (batched ops
are deterministic under duplicates, version bumps count touched rows once),
lazy_grads pad with masked-out entries.

``nn_search`` additionally has an engine-level ``search_mode``: ``"exact"``
(brute force over the bank — reference or blocked Pallas kernel) or
``"ivf"`` (two-stage search against the asynchronously-clustered index from
``repro.core.ann_index`` / ``repro.kernels.nn_search_ivf``), overridable
per request and falling back to exact whenever the index is absent or past
its staleness budget. On the sharded backend the engine maintains a
``ShardedIVFIndex`` — one sub-index per shard, per-shard write counters,
per-shard independent rebuilds — and serves IVF queries through the
hierarchical merge in ``repro.core.sharded_kb.sharded_kb_nn_search_ivf``.

State ownership: lookup, update and lazy_grad DONATE the bank state to
their jitted program, which scatters its B rows in place and returns the
new state; the old state's buffers are deleted. Only the engine's current
``state`` is live, and a caller that wants an older one must copy it
before the next op. ``donation_misses`` counts the dispatches after which
a leaf of the donated state was not consumed (XLA did not alias it, or a
host view held it). It cannot see a copy made inside a program that
aliases its input, as the int8 fused lookup's Pallas kernel, which
declares no ``input_output_aliases``, makes of ``table`` and ``grad_sum``;
the compile tests and the device trace show those. ``flush`` and the
tiered fault-in and ``import_rows`` scatters do not donate.

The engine itself is NOT thread-safe — concurrency (locking or request
coalescing) is the server layer's job. The one sanctioned exception: the
``IVFRefresher`` thread snapshots the table, reads ``total_write_rows`` /
``shard_write_rows`` and swaps ``ann_index``. The snapshot is an on-device
copy taken under ``swap_lock``, which every donating dispatch also holds,
so no donation can delete the table mid-read; the copy moves to the host
outside the lock. ``ann_index`` is an atomic attribute store of an
immutable value; ``shard_write_rows`` is a numpy array the owner mutates
in place (monotonic ``+=``), so the refresher may read a value stale by
the in-flight batch — which only UNDERSTATES staleness by that batch,
deferring (never corrupting) a rebuild, and the post-build clock snapshot
is taken before the table read so concurrent writes still count as
staleness against the new index.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knowledge_bank as kbm
from repro.core.kb_storage import make_cold_store
from repro.core.knowledge_bank import KBState
from repro.sharding.partition import DistContext, local_dist


class KBBackend(Protocol):
    """Functional KB ops over a shared ``KBState``. All ids/grads flat."""

    name: str

    def lookup(self, state: KBState, ids, *, lazy_lr: float, zmax: float,
               apply_pending: bool = True) -> Tuple[jnp.ndarray, KBState]: ...

    def update(self, state: KBState, ids, values) -> KBState: ...

    def lazy_grad(self, state: KBState, ids, grads, *, zmax: float,
                  mask=None) -> KBState: ...

    def flush(self, state: KBState, *, lazy_lr: float,
              zmax: float) -> KBState: ...

    def nn_search(self, state: KBState, queries, k: int,
                  *, exclude_ids=None) -> Tuple[jnp.ndarray, jnp.ndarray]: ...


class DenseBackend:
    """The jnp reference ops — semantics ground truth for every backend."""

    name = "dense"

    def lookup(self, state, ids, *, lazy_lr, zmax, apply_pending=True):
        return kbm.kb_lookup(state, ids, lazy_lr=lazy_lr, zmax=zmax,
                             apply_pending=apply_pending)

    def update(self, state, ids, values):
        return kbm.kb_update(state, ids, values)

    def lazy_grad(self, state, ids, grads, *, zmax, mask=None):
        return kbm.kb_lazy_grad(state, ids, grads, zmax=zmax, mask=mask)

    def flush(self, state, *, lazy_lr, zmax):
        return kbm.kb_flush(state, lazy_lr=lazy_lr, zmax=zmax)

    def nn_search(self, state, queries, k, *, exclude_ids=None):
        return kbm.kb_nn_search(state, queries, k, exclude_ids=exclude_ids)


class ShardedBackend:
    """Mesh-sharded ops: owner-masked scatters, one psum fan-in per lookup.
    See repro.core.sharded_kb for the communication analysis. Without a
    ``DistContext`` that carries a mesh, the bank spans every local device
    (``repro.sharding.partition.local_dist``)."""

    name = "sharded"

    def __init__(self, dist: Optional[DistContext] = None, *,
                 use_nn_kernel: bool = False):
        from repro.core import sharded_kb as skb
        if dist is None or dist.mesh is None:
            dist = local_dist()
        self.dist = dist
        self.use_nn_kernel = use_nn_kernel
        self._skb = skb

    def lookup(self, state, ids, *, lazy_lr, zmax, apply_pending=True):
        return self._skb.sharded_kb_lookup(state, ids, self.dist,
                                           lazy_lr=lazy_lr, zmax=zmax,
                                           apply_pending=apply_pending)

    def update(self, state, ids, values):
        return self._skb.sharded_kb_update(state, ids, values, self.dist)

    def lazy_grad(self, state, ids, grads, *, zmax, mask=None):
        return self._skb.sharded_kb_lazy_grad(state, ids, grads, self.dist,
                                              zmax=zmax, mask=mask)

    def flush(self, state, *, lazy_lr, zmax):
        return self._skb.sharded_kb_flush(state, self.dist, lazy_lr=lazy_lr,
                                          zmax=zmax)

    def nn_search(self, state, queries, k, *, exclude_ids=None):
        if exclude_ids is None:
            return self._skb.sharded_kb_nn_search(
                state, queries, k, self.dist, use_kernel=self.use_nn_kernel)
        from repro.kernels.nn_search import overfetch_exclude_topk
        return overfetch_exclude_topk(
            lambda kk: self._skb.sharded_kb_nn_search(
                state, queries, kk, self.dist,
                use_kernel=self.use_nn_kernel),
            state.table.shape[0], k, exclude_ids)

    def nn_search_ivf(self, table, centroids, packed_vecs, packed_ids,
                      queries, k, nprobe):
        """Hierarchical sub-linear search over per-shard sub-indexes (see
        ``repro.core.sharded_kb.sharded_kb_nn_search_ivf``). Deterministic
        pure function of (index, table, queries) — coalescing-safe."""
        return self._skb.sharded_kb_nn_search_ivf(
            table, centroids, packed_vecs, packed_ids, queries, k, nprobe,
            self.dist)

    def nn_search_ivf_q(self, table, centroids, packed_codes, packed_scale,
                        packed_offset, packed_ids, queries, k, nprobe):
        """Quantized-snapshot variant: int8 packed sub-index rows scored via
        the affine decomposition ``s (q.c) + o sum(q)``; the live re-rank
        still runs against the fp32 sharded table, so returned scores stay
        exact (quantization costs shortlist recall only)."""
        return self._skb.sharded_kb_nn_search_ivf(
            table, centroids, packed_codes, packed_ids, queries, k, nprobe,
            self.dist, packed_scale=packed_scale,
            packed_offset=packed_offset)

    def create(self, num_entries: int, dim: int, *, dtype,
               key) -> KBState:
        """A ``KBState`` born sharded over the mesh: every device
        materializes only the rows it owns, so a bank sized for the whole
        mesh never passes through one device."""
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self.dist.mesh
        shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            self._skb.kb_pspecs(self.dist),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        return jax.jit(lambda: kbm.kb_create(num_entries, dim, dtype=dtype,
                                             key=key),
                       out_shardings=shardings)()

    def put_rows(self, arr) -> jax.Array:
        """A host array sharded over the mesh rows by its leading axis —
        the layout ``sharded_kb_nn_search_ivf`` reads its sub-indexes in,
        so neither a build nor a search moves index bytes through one
        device."""
        from jax.sharding import NamedSharding, PartitionSpec
        spec = PartitionSpec(self._skb.kb_axes(self.dist),
                             *([None] * (np.ndim(arr) - 1)))
        return jax.device_put(arr, NamedSharding(self.dist.mesh, spec))

    @property
    def n_shards(self) -> int:
        """Total bank shards = product of the mesh axes the rows span."""
        mesh = self.dist.mesh
        return int(np.prod([mesh.shape[a]
                            for a in self._skb.kb_axes(self.dist)]))


class PallasBackend:
    """TPU serving path: Pallas kernels for the ops that scan the bank.

    ``flush`` and ``nn_search`` visit every row, so they run as blocked
    kernels. ``lookup`` touches only its B requested rows, so it gathers
    them and their caches by id, applies the clipped pending average and
    scatters them back with their caches cleared: the dense reference's
    own XLA gather and scatter. A kernel that streams all N rows to find
    its B would move N·D bytes a call instead of B·D.

    ``interpret=None`` (default) resolves ONCE at construction from the
    process ``KernelConfig`` (repro.env): interpret mode on CPU, compiled
    on an accelerator backend."""

    name = "pallas"

    def __init__(self, *, interpret: Optional[bool] = None):
        from repro.env import resolve_interpret
        self.interpret = resolve_interpret(interpret)

    def lookup(self, state, ids, *, lazy_lr, zmax, apply_pending=True):
        return kbm.kb_lookup(state, ids, lazy_lr=lazy_lr, zmax=zmax,
                             apply_pending=apply_pending)

    def update(self, state, ids, values):
        return kbm.kb_update(state, ids, values)

    def lazy_grad(self, state, ids, grads, *, zmax, mask=None):
        return kbm.kb_lazy_grad(state, ids, grads, zmax=zmax, mask=mask)

    def flush(self, state, *, lazy_lr, zmax):
        from repro.kernels.lazy_apply import lazy_apply_pallas
        tbl, gsum, gcnt, gsq = lazy_apply_pallas(
            state.table, state.grad_sum, state.grad_cnt, state.grad_sqnorm,
            lazy_lr=lazy_lr, zmax=zmax, interpret=self.interpret)
        return state._replace(
            table=tbl,
            version=state.version + (state.grad_cnt > 0).astype(jnp.int32),
            grad_sum=gsum, grad_cnt=gcnt, grad_sqnorm=gsq,
            step=state.step + 1)

    def nn_search(self, state, queries, k, *, exclude_ids=None):
        from repro.kernels.nn_search import (nn_search_pallas,
                                             overfetch_exclude_topk)
        search = (lambda kk: nn_search_pallas(queries, state.table, kk,
                                              interpret=self.interpret))
        if exclude_ids is None:
            return search(k)
        return overfetch_exclude_topk(search, state.table.shape[0], k,
                                      exclude_ids)


def make_backend(name: str, *, dist: Optional[DistContext] = None,
                 interpret: Optional[bool] = None) -> KBBackend:
    """Backend factory: ``dense | sharded | pallas``. All three satisfy
    the same contract — bit-identical state evolution on the same op
    sequence (tests/test_kb_engine.py) — so callers may switch backends
    without revalidating semantics."""
    if name == "dense":
        return DenseBackend()
    if name == "sharded":
        return ShardedBackend(dist)
    if name == "pallas":
        return PallasBackend(interpret=interpret)
    raise ValueError(f"unknown KB backend {name!r} "
                     "(want dense | sharded | pallas)")


class KBOps(NamedTuple):
    """In-graph functional facade over one ``KBBackend``.

    The trainer's step builders and the knowledge makers are JITTED
    programs that thread a ``KBState`` through themselves — they cannot
    talk to the host-side ``KBEngine``/``KnowledgeBankServer``. ``KBOps``
    is their view of the engine: four pure closures, selected ONCE per
    backend by ``make_kb_ops`` and traceable inside jit, so no call site
    ever branches on the mesh again. Backend dispatch lives here and in
    ``make_backend`` — nowhere else.

    Every closure has the dense reference semantics (backends are
    bit-identical, see module docstring); the lazy-update knobs
    (``lazy_lr`` / ``zmax`` / ``apply_pending``) are bound at construction
    so callers carry no config.

    - ``lookup(kb, ids)``                       -> (values, kb')
    - ``update(kb, ids, values)``               -> kb'
    - ``lazy_grad(kb, ids, grads)``             -> kb'
    - ``nn_search(kb, q, k, *, exclude_ids=None)`` -> (scores, ids)
    - ``flush(kb)``                             -> kb'
    """

    lookup: Callable
    update: Callable
    lazy_grad: Callable
    nn_search: Callable
    flush: Callable
    backend_name: str


def make_kb_ops(dist: Optional[DistContext] = None, *,
                backend=None, lazy_lr: float = 0.1, zmax: float = 3.0,
                apply_pending: bool = True,
                interpret: Optional[bool] = None) -> KBOps:
    """Select a backend once and bind the lazy-update knobs into a
    ``KBOps`` bundle.

    ``backend`` may be a ``KBBackend`` instance or a factory name; when
    omitted the choice follows the mesh — ``sharded`` iff ``dist`` carries
    one, else ``dense`` — which is the single place the old per-callsite
    ``if dist.mesh is not None`` dispatch now lives."""
    if backend is None:
        backend = ("sharded" if dist is not None and dist.mesh is not None
                   else "dense")
    bk = (backend if not isinstance(backend, str)
          else make_backend(backend, dist=dist, interpret=interpret))
    return KBOps(
        lookup=lambda kb, ids: bk.lookup(kb, ids, lazy_lr=lazy_lr,
                                         zmax=zmax,
                                         apply_pending=apply_pending),
        update=lambda kb, ids, values: bk.update(kb, ids, values),
        lazy_grad=lambda kb, ids, grads: bk.lazy_grad(kb, ids, grads,
                                                      zmax=zmax),
        nn_search=lambda kb, q, k, *, exclude_ids=None: bk.nn_search(
            kb, q, k, exclude_ids=exclude_ids),
        flush=lambda kb: bk.flush(kb, lazy_lr=lazy_lr, zmax=zmax),
        backend_name=bk.name,
    )


def _engine_op(fn):
    """Run a ``KBEngine`` op method inside a ``kb.engine.<op>`` host span
    tagged with the server's run number, and add its host seconds to
    ``op_s``. The span is recorded only while the profiler runs."""
    name = f"kb.engine.{fn.__name__}"

    @functools.wraps(fn)
    def op(self, *args, **kw):
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, run=self.current_run):
                return fn(self, *args, **kw)
        finally:
            self.op_s += time.perf_counter() - t
    return op


def _bucket(n: int, minimum: int = 8) -> int:
    """Next power-of-two jit bucket (>= minimum)."""
    return max(minimum, 1 << max(n - 1, 0).bit_length())


class KBEngine:
    """Stateful, host-facing shell around a ``KBBackend``.

    numpy in / numpy out; every device call is a jitted batched op over a
    power-of-two-padded batch, so the compiled-program set stays bounded no
    matter what request sizes the server coalesces. Single-threaded by
    contract (see module docstring)."""

    def __init__(self, num_entries: int, dim: int, *,
                 backend="dense", dist: Optional[DistContext] = None,
                 lazy_lr: float = 0.1, zmax: float = 3.0,
                 entry_zmax: Optional[float] = None,
                 lazy_update: bool = True,
                 interpret: Optional[bool] = None,
                 search_mode: str = "exact", ann_nlist: int = 64,
                 ann_nprobe: int = 8, ann_stale_rows: Optional[int] = None,
                 dtype=jnp.float32, key: Optional[jax.Array] = None,
                 storage: str = "fp32", master_rows: int = 1024,
                 resident_rows: Optional[int] = None,
                 cold_after_rows: Optional[int] = None,
                 cold_dir: Optional[str] = None):
        self.backend: KBBackend = (backend if not isinstance(backend, str)
                                   else make_backend(backend, dist=dist,
                                                     interpret=interpret))
        self.num_entries, self.dim = num_entries, dim
        self.lazy_lr, self.zmax, self.lazy_update = lazy_lr, zmax, lazy_update
        # -- storage mode (tentpole: int8 rows + two-tier residency) ------
        if storage not in ("fp32", "int8"):
            raise ValueError(f"unknown storage {storage!r} "
                             "(want fp32 | int8)")
        self.storage = storage
        sharded = isinstance(self.backend, ShardedBackend)
        # int8 quantizes the LIVE table on the single-device backends; the
        # sharded backend keeps its fp32 table (mesh specs untouched) and
        # quantizes the IVF snapshot instead — see rebuild_ann_index.
        self._quantized = storage == "int8" and not sharded
        if storage == "int8" and not lazy_update:
            raise ValueError(
                "storage='int8' requires lazy_update=True: the immediate-"
                "mode ablation scatter-adds into the table, which is not "
                "defined over int8 codes")
        tiered = resident_rows is not None
        if cold_after_rows is not None and not tiered:
            raise ValueError("cold_after_rows needs resident_rows set")
        if tiered and sharded:
            raise ValueError("tiered residency is single-device only "
                             "(dense | pallas backends)")
        if tiered and key is not None:
            raise ValueError(
                "tiered residency requires key=None: non-resident rows "
                "materialize as zeros on first touch, so a random init "
                "would make residency observable")
        if tiered and not 0 < resident_rows <= num_entries:
            raise ValueError(f"resident_rows={resident_rows} out of range "
                             f"(1..{num_entries})")
        self.tiered = tiered
        self.master_rows = master_rows
        self.cold_after_rows = cold_after_rows
        if search_mode not in ("exact", "ivf"):
            raise ValueError(f"unknown search_mode {search_mode!r} "
                             "(want exact | ivf)")
        # -- ANN (IVF) serving state; see repro.core.ann_index ------------
        self.search_mode = search_mode
        self.ann_nlist, self.ann_nprobe = ann_nlist, ann_nprobe
        # exact fallback once this many rows were written since the build;
        # default: the whole bank rewritten
        self.ann_stale_rows = (num_entries if ann_stale_rows is None
                               else ann_stale_rows)
        self.ann_index = None               # swapped in by the refresher
        self.total_write_rows = 0           # monotonic; written-row counter
        # per-shard write counters drive per-shard sub-index rebuilds on the
        # sharded backend; everywhere else there is exactly one "shard"
        self.ann_shards = (self.backend.n_shards
                           if isinstance(self.backend, ShardedBackend)
                           else 1)
        if num_entries % self.ann_shards:
            raise ValueError(f"num_entries={num_entries} not divisible by "
                             f"{self.ann_shards} bank shards")
        self.shard_write_rows = np.zeros((self.ann_shards,), np.int64)
        self._ann_shard_built_at = np.zeros((self.ann_shards,), np.int64)
        self.search_stats = {"exact": 0, "ivf": 0}
        self._ivf_fns = {}
        # entry-side (per-contribution EMA) clip; defaults to the apply-side
        # zmax, matching the per-call server's single knob
        entry_zmax = zmax if entry_zmax is None else entry_zmax
        # tiered engines size the device state to the resident slots only;
        # everything else lives in the cold store until first touch
        rows = resident_rows if tiered else num_entries
        self.resident_rows = rows
        if self._quantized:
            if key is not None:
                st = kbm.kb_create(rows, dim, key=key)
                codes, s, o = kbm.quantize_rows(st.table)
                self.state = st._replace(table=codes)
                self._qscale, self._qoffset = s, o
            else:
                # zero rows quantize to (codes 0, scale 1, offset 0):
                # dequant is exactly 0.0, matching the fp32 zero init
                self.state = kbm.kb_create(rows, dim, dtype=jnp.int8)
                self._qscale = jnp.ones((rows,), jnp.float32)
                self._qoffset = jnp.zeros((rows,), jnp.float32)
        elif sharded:
            self.state = self.backend.create(rows, dim, dtype=dtype, key=key)
            self._qscale = self._qoffset = None
        else:
            self.state = kbm.kb_create(rows, dim, dtype=dtype, key=key)
            self._qscale = self._qoffset = None
        # -- two-tier residency bookkeeping (host-side, O(N) ints) --------
        if tiered:
            self.cold_store = make_cold_store(cold_dir)
            self._slot_of = np.full((num_entries,), -1, np.int64)
            self._slot_id = np.full((rows,), -1, np.int64)
            self._free_slots = list(range(rows - 1, -1, -1))
            self._touch = np.zeros((num_entries,), np.int64)
            self._gen = 0           # write clock: += distinct rows written
        else:
            self.cold_store = None
        self.tier_faults = 0        # rows restored from the cold store
        self.tier_spills = 0        # rows pushed down to the cold store
        # fp32 master set: exact rows (as pushed by update) for final-score
        # re-ranking in int8 mode; invalidated per-id by lazy_grad
        self._masters: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self.dispatches = 0         # device calls issued (bench metric)
        # host seconds inside the op methods, and the parts of them spent
        # placing arguments on the device (``kb.engine.args``) and blocked
        # on a result's device-to-host copy (``kb.engine.wait``)
        self.op_s = 0.0
        self.args_s = 0.0
        self.wait_s = 0.0
        # the server's run number while it calls an op (-1: a direct
        # call); tags the ``kb.engine.*`` spans
        self.current_run = -1
        # held by every dispatch that donates the state, and by the IVF
        # refresher while it copies the table (module docstring)
        self.swap_lock = threading.Lock()
        self.donation_misses = 0

        bk = self.backend
        if self._quantized:
            if isinstance(bk, PallasBackend):
                from repro.kernels.kb_fused_lookup import (
                    kb_fused_lookup_q_pallas)
                interp = bk.interpret

                def kb_lookup_q(st, qs, qo, ids):
                    vals, tbl, s, o, gsum, gcnt, gsq = (
                        kb_fused_lookup_q_pallas(
                            st.table, qs, qo, st.grad_sum, st.grad_cnt,
                            st.grad_sqnorm, ids, lazy_lr=lazy_lr, zmax=zmax,
                            interpret=interp))
                    touched = jnp.zeros(st.version.shape, bool).at[ids].set(
                        True, mode="drop")
                    version = st.version + (
                        touched & (st.grad_cnt > 0)).astype(jnp.int32)
                    st = st._replace(table=tbl, version=version,
                                     grad_sum=gsum, grad_cnt=gcnt,
                                     grad_sqnorm=gsq)
                    return vals, st, s, o
            else:
                def kb_lookup_q(st, qs, qo, ids):
                    return kbm.kb_lookup_q(st, qs, qo, ids,
                                           lazy_lr=lazy_lr, zmax=zmax)

            def kb_update(st, qs, qo, ids, v):
                return kbm.kb_update_q(st, qs, qo, ids, v)

            def kb_flush(st, qs, qo):
                return kbm.kb_flush_q(st, qs, qo, lazy_lr=lazy_lr, zmax=zmax)

            self._lookup_fn = jax.jit(kb_lookup_q, donate_argnums=(0, 1, 2))
        else:
            def kb_lookup(st, ids):
                return bk.lookup(st, ids, lazy_lr=lazy_lr, zmax=zmax,
                                 apply_pending=lazy_update)

            def kb_update(st, ids, v):
                return bk.update(st, ids, v)

            def kb_flush(st):
                return bk.flush(st, lazy_lr=lazy_lr, zmax=zmax)

            self._lookup_fn = jax.jit(kb_lookup, donate_argnums=0)
        # each jitted op is a named function, not a lambda: XLA names the
        # device ops in a profile after it. Every op that returns a new
        # state donates the old one (module docstring), except flush
        self._update_fn = jax.jit(
            kb_update, donate_argnums=(0, 1, 2) if self._quantized else 0)
        self._flush_fn = jax.jit(kb_flush)

        # lazy_grad only touches the fp32 gradient caches — never the table
        # — so the fp32 op serves both storage modes unchanged
        def kb_lazy_grad(st, ids, g, m):
            return bk.lazy_grad(st, ids, g, zmax=entry_zmax, mask=m)

        # ablation baseline: immediate SGD scatter, no cache (lazy_update
        # off). mask keeps padded entries inert (g * 0).
        def kb_immediate_grad(st, ids, g, m):
            return st._replace(table=st.table.at[ids].add(
                (-lazy_lr * g * m[:, None]).astype(st.table.dtype)))

        self._lazy_fn = jax.jit(kb_lazy_grad, donate_argnums=0)
        self._immediate_fn = jax.jit(kb_immediate_grad, donate_argnums=0)
        self._nn_fns = {}

    def _put(self, *arrays) -> tuple:
        """Place an op's host arguments on the device, inside a
        ``kb.engine.args`` span tagged with the run and the bank's shard
        count, and add the time to ``args_s``."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("kb.engine.args",
                                          run=self.current_run,
                                          shards=self.ann_shards):
            out = tuple(jnp.asarray(a) for a in arrays)
        self.args_s += time.perf_counter() - t
        return out

    def _fetch(self, *arrays) -> tuple:
        """Copy an op's results to the host: its one wait on the device,
        inside a ``kb.engine.wait`` span and added to ``wait_s``."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("kb.engine.wait"):
            out = tuple(np.asarray(a) for a in arrays)
        self.wait_s += time.perf_counter() - t
        return out

    def _count_miss(self, donated) -> None:
        """After a dispatch that donated ``donated``: count a miss if any
        of its leaves was not consumed (XLA did not alias it, or a host
        view held it)."""
        if not all(a.is_deleted() for a in jax.tree.leaves(donated)):
            self.donation_misses += 1

    # -- embedding ops -----------------------------------------------------

    @_engine_op
    def lookup(self, ids) -> np.ndarray:
        """Fetch rows (applying pending lazy updates first); any id shape.
        Deterministic under duplicate ids and pow2 padding (pads with a
        duplicated real entry; version bumps count each touched row once)
        — the invariant that lets the server merge concurrent lookups
        into one batch and slice the result per caller."""
        ids = np.asarray(ids)
        flat = ids.reshape(-1).astype(np.int32)
        if flat.size == 0:
            return np.zeros((*ids.shape, self.dim), np.float32)
        dev = self._admit(flat)
        pad = _bucket(dev.size) - dev.size
        padded = np.concatenate([dev, np.full(pad, dev[-1], np.int32)])
        (padded,) = self._put(padded)
        donated = (self.state, self._qscale, self._qoffset)
        with self.swap_lock:
            if self._quantized:
                vals, self.state, self._qscale, self._qoffset = (
                    self._lookup_fn(*donated, padded))
            else:
                vals, self.state = self._lookup_fn(self.state, padded)
        self._count_miss(donated)
        self.dispatches += 1
        (vals,) = self._fetch(vals[:flat.size])
        return vals.reshape(*ids.shape, -1)

    @_engine_op
    def update(self, ids, values) -> None:
        """Direct write (maker push); duplicate ids resolve last-writer-wins
        (host-side dedupe — device scatter order is unspecified). Each
        distinct row is charged once to the global and per-shard ANN
        staleness clocks."""
        ids = np.asarray(ids).reshape(-1).astype(np.int32)
        if ids.size == 0:
            return
        values = np.asarray(values).reshape(ids.size, -1)
        _, keep = np.unique(ids[::-1], return_index=True)
        keep = ids.size - 1 - keep          # last occurrence of each id
        ids, values = ids[keep], values[keep]
        n = ids.size                        # distinct rows, pre-padding
        if self._quantized and self.master_rows > 0:
            # masters hold the PRE-quantization rows: update() is the one
            # op with exact fp32 values in hand
            for i in range(n):
                g = int(ids[i])
                self._masters[g] = values[i].astype(np.float32).copy()
                self._masters.move_to_end(g)
                if len(self._masters) > self.master_rows:
                    self._masters.popitem(last=False)
        dev = self._admit(ids)
        pad = _bucket(n) - n
        dev_p = np.concatenate([dev, np.full(pad, dev[-1], np.int32)])
        values_p = np.concatenate([values, np.repeat(values[-1:], pad, 0)])
        dev_p, values_p = self._put(dev_p, values_p)
        donated = (self.state, self._qscale, self._qoffset)
        with self.swap_lock:
            if self._quantized:
                self.state, self._qscale, self._qoffset = self._update_fn(
                    *donated, dev_p, values_p)
            else:
                self.state = self._update_fn(self.state, dev_p, values_p)
        self._count_miss(donated)
        self.dispatches += 1
        self._count_writes(ids)
        if self.tiered:
            self._gen += n
            self._spill_cold()

    @_engine_op
    def lazy_grad(self, ids, grads) -> None:
        """Cache gradients (or apply immediately when lazy_update=False).
        Padded entries carry a 0 mask and are inert; cache adds commute,
        so a coalesced multi-client batch equals any serial interleaving.
        Charges the touched rows to the (per-shard) ANN staleness clock —
        the cached gradient WILL reach the table."""
        ids = np.asarray(ids).reshape(-1).astype(np.int32)
        if ids.size == 0:
            return
        grads = np.asarray(grads, np.float32).reshape(ids.size, -1)
        if self._quantized and self._masters:
            # these rows' live values diverge from their masters the moment
            # the cached gradient applies — drop the stale exact copies
            for g in np.unique(ids):
                self._masters.pop(int(g), None)
        dev = self._admit(ids)
        n = ids.size
        pad = _bucket(n) - n
        ids_p = np.concatenate([dev, np.full(pad, dev[-1], np.int32)])
        grads_p = np.concatenate([grads, np.zeros((pad, grads.shape[1]),
                                                  np.float32)])
        mask = np.concatenate([np.ones(n, np.float32),
                               np.zeros(pad, np.float32)])
        fn = self._lazy_fn if self.lazy_update else self._immediate_fn
        args = self._put(ids_p, grads_p, mask)
        donated = self.state
        with self.swap_lock:
            self.state = fn(donated, *args)
        self._count_miss(donated)
        self.dispatches += 1
        # row mutation volume for ANN staleness: a cached gradient WILL be
        # applied (next lookup or flush), immediate mode scatters now —
        # either way these rows' vectors diverge from the index snapshot.
        # Counting here (not at lookup) keeps pure reads free: a read-only
        # workload never triggers rebuilds or the stale fallback.
        self._count_writes(ids)
        if self.tiered:
            self._gen += int(np.unique(ids).size)
            self._spill_cold()

    # -- two-tier residency (resident device slots + host/disk cold store) -

    def _admit(self, flat: np.ndarray) -> np.ndarray:
        """Tiered engines: fault this batch's rows device-resident and
        translate global ids -> device slots (identity otherwise). Eviction
        is oldest-touch-first among resident rows NOT in the current batch;
        a batch with more distinct rows than there are slots cannot be
        served and raises."""
        if not self.tiered:
            return flat
        # out-of-range ids clamp to the edge row — the same net behavior a
        # jitted device gather gives the non-tiered engines
        flat = np.clip(flat, 0, self.num_entries - 1).astype(np.int32)
        uniq = np.unique(flat)
        miss = uniq[self._slot_of[uniq] < 0]
        if miss.size:
            short = miss.size - len(self._free_slots)
            if short > 0:
                res = np.flatnonzero(self._slot_id >= 0)
                cand = res[~np.isin(self._slot_id[res], uniq)]
                if cand.size < short:
                    raise ValueError(
                        f"batch touches {uniq.size} distinct rows but only "
                        f"{self.resident_rows} device slots exist")
                order = np.argsort(self._touch[self._slot_id[cand]],
                                   kind="stable")
                self._spill_slots(cand[order[:short]])
            self._fault_in(miss)
        self._touch[uniq] = self._gen
        return self._slot_of[flat].astype(np.int32)

    def _fault_in(self, gids: np.ndarray) -> None:
        """Restore rows from the cold store (or materialize zero rows on
        first-ever touch) into free slots — the FULL per-row state, so the
        round trip is bit-identical. Slot contents changing under a built
        IVF index is row churn, so faults charge the staleness clock."""
        n = gids.size
        slots = np.array([self._free_slots.pop() for _ in range(n)],
                         np.int64)
        st = self.state
        rows = np.zeros((n, self.dim), st.table.dtype)
        ver = np.zeros((n,), np.int32)
        gsum = np.zeros((n, self.dim), np.float32)
        gcnt = np.zeros((n,), np.float32)
        gsq = np.zeros((n,), np.float32)
        ema = np.zeros((n,), np.float32)
        scl = np.ones((n,), np.float32)
        off = np.zeros((n,), np.float32)
        for i in range(n):
            rec = self.cold_store.get(int(gids[i]))
            if rec is None:
                continue                        # first touch: zero row
            self.tier_faults += 1
            rows[i], ver[i] = rec["table"], rec["version"]
            gsum[i], gcnt[i] = rec["grad_sum"], rec["grad_cnt"]
            gsq[i], ema[i] = rec["grad_sqnorm"], rec["norm_ema"]
            if self._quantized:
                scl[i], off[i] = rec["scale"], rec["offset"]
        idx = jnp.asarray(slots)
        self.state = st._replace(
            table=st.table.at[idx].set(jnp.asarray(rows)),
            version=st.version.at[idx].set(jnp.asarray(ver)),
            grad_sum=st.grad_sum.at[idx].set(jnp.asarray(gsum)),
            grad_cnt=st.grad_cnt.at[idx].set(jnp.asarray(gcnt)),
            grad_sqnorm=st.grad_sqnorm.at[idx].set(jnp.asarray(gsq)),
            norm_ema=st.norm_ema.at[idx].set(jnp.asarray(ema)))
        if self._quantized:
            self._qscale = self._qscale.at[idx].set(jnp.asarray(scl))
            self._qoffset = self._qoffset.at[idx].set(jnp.asarray(off))
        self._slot_of[gids] = slots
        self._slot_id[slots] = gids
        self._count_writes(gids.astype(np.int32))

    def _spill_slots(self, slots: np.ndarray) -> None:
        """Push resident slots down to the cold store (full per-row state)
        and free them. The freed slots keep their stale device contents —
        harmless, because ``_slot_id`` = -1 masks them out of nn_search and
        the next fault-in overwrites every leaf."""
        if slots.size == 0:
            return
        idx = jnp.asarray(slots)
        st = self.state
        rows = np.asarray(st.table[idx])
        ver = np.asarray(st.version[idx])
        gsum = np.asarray(st.grad_sum[idx])
        gcnt = np.asarray(st.grad_cnt[idx])
        gsq = np.asarray(st.grad_sqnorm[idx])
        ema = np.asarray(st.norm_ema[idx])
        if self._quantized:
            scl = np.asarray(self._qscale[idx])
            off = np.asarray(self._qoffset[idx])
        for i, s in enumerate(slots):
            rec = {"table": rows[i], "version": ver[i], "grad_sum": gsum[i],
                   "grad_cnt": gcnt[i], "grad_sqnorm": gsq[i],
                   "norm_ema": ema[i]}
            if self._quantized:
                rec["scale"], rec["offset"] = scl[i], off[i]
            g = int(self._slot_id[s])
            self.cold_store.put(g, rec)
            self._slot_of[g] = -1
            self._slot_id[s] = -1
            self._free_slots.append(int(s))
        self.tier_spills += int(slots.size)

    def _spill_cold(self) -> None:
        """Proactive spill after a write op: rows untouched for at least
        ``cold_after_rows`` write-generations leave the device. O(resident)
        scan — never walks the full id space."""
        if self.cold_after_rows is None:
            return
        res = np.flatnonzero(self._slot_id >= 0)
        if res.size == 0:
            return
        age = self._gen - self._touch[self._slot_id[res]]
        self._spill_slots(res[age >= self.cold_after_rows])

    def _count_writes(self, ids: np.ndarray) -> None:
        """Charge written rows to the global AND per-shard staleness
        counters (shard = contiguous owner range, the ``OwnerShard`` rule).
        Per-shard counts let the refresher rebuild one hot shard's
        sub-index without touching the cold ones."""
        self.total_write_rows += ids.size
        if self.ann_shards == 1:
            self.shard_write_rows[0] += ids.size
        else:
            n_local = self.num_entries // self.ann_shards
            # clip out-of-range ids to the edge shards: the device scatter
            # drops foreign lanes harmlessly, so host accounting must not
            # be the path that turns a bad id into a crash
            self.shard_write_rows += np.bincount(
                np.clip(ids // n_local, 0, self.ann_shards - 1),
                minlength=self.ann_shards).astype(np.int64)

    @_engine_op
    def flush(self) -> None:
        """Expiration path: apply every pending cached gradient now.
        (Flushed rows were already counted toward ``total_write_rows`` when
        their gradients were cached.) Tiered engines flush the RESIDENT
        tier; a cold row's pending gradients travel with its spilled state
        and apply on fault-in — same lazy semantics, later clock."""
        if self._quantized:
            self.state, self._qscale, self._qoffset = self._flush_fn(
                self.state, self._qscale, self._qoffset)
        else:
            self.state = self._flush_fn(self.state)
        self.dispatches += 1

    @_engine_op
    def nn_search(self, queries, k: int, *, mode: Optional[str] = None,
                  exclude_ids: Optional[np.ndarray] = None
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k MIPS over the bank. ``mode`` overrides the engine-level
        ``search_mode`` per request; ``"ivf"`` silently falls back to the
        exact path when the index is absent or too stale (within budget,
        staleness costs recall only — winners are re-scored against the
        live table, so returned scores are always exact for the returned
        ids). ``exclude_ids`` (B, E) int32, -1 = no-op, bans rows per
        query: the engine over-fetches ``k+E`` through whichever path is
        live (IVF included — a query can exclude at most E rows, so k
        unbanned candidates always survive; on the exact path this equals
        the backend's pre-mask top-k) and masks host-side. Deterministic
        for a fixed (state, index): the server may merge same-(k, mode,
        E) requests into one batched call and slice the results without
        changing any caller's answer."""
        queries = np.asarray(queries, np.float32)
        if exclude_ids is None:
            return self._nn_topk(queries, k, mode)
        excl = np.asarray(exclude_ids, np.int32).reshape(queries.shape[0],
                                                           -1)
        scores, ids = self._nn_topk(queries, k + excl.shape[1], mode)
        banned = ((ids[:, :, None] == excl[:, None, :])
                  & (excl[:, None, :] >= 0)).any(-1)
        scores = np.where(banned, -np.inf, scores)
        ids = np.where(banned, -1, ids)
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        return (np.take_along_axis(scores, order, 1),
                np.take_along_axis(ids, order, 1))

    def _nn_topk(self, queries: np.ndarray, k: int, mode: Optional[str]
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """``nn_search`` without exclusions: one padded device call."""
        B = queries.shape[0]
        pad = _bucket(B) - B
        q = np.concatenate([queries, np.zeros((pad, queries.shape[1]),
                                              np.float32)])
        mode = self.search_mode if mode is None else mode
        if mode not in ("exact", "ivf"):
            raise ValueError(f"unknown nn_search mode {mode!r} "
                             "(want exact | ivf)")
        idx = self.ann_index
        use_ivf = (mode == "ivf" and idx is not None
                   and getattr(idx, "n_shards", 1) == self.ann_shards
                   and self.ann_staleness_rows <= self.ann_stale_rows)
        if use_ivf:
            kq = k
            if self._quantized:
                # over-retrieve 4x so the fp32 master re-rank can recover
                # near-ties the int8 shortlist mis-ordered (the sharded
                # path does the same inside its hierarchical merge)
                pool = int(idx.bucket_cap) * min(self.ann_nprobe,
                                                 int(idx.nlist))
                kq = max(k, min(4 * k, pool))
            scores, ids = self._ivf_search(q, kq, idx)
            self.search_stats["ivf"] += 1
        else:
            if k not in self._nn_fns:
                bk = self.backend
                if self._quantized:
                    # exact MIPS over int8 codes via the affine
                    # decomposition — no dequantized (N, D) materialized
                    # (the blocked fp32 Pallas kernel has no int8 twin;
                    # int8 serving is expected to run IVF anyway)
                    def kb_nn_exact(st, qs, qo, q):
                        return kbm.kb_nn_search_q(st, qs, qo, q, k)
                else:
                    def kb_nn_exact(st, q):
                        return bk.nn_search(st, q, k)
                self._nn_fns[k] = jax.jit(kb_nn_exact)
            if self._quantized:
                scores, ids = self._nn_fns[k](self.state, self._qscale,
                                              self._qoffset, jnp.asarray(q))
            else:
                scores, ids = self._nn_fns[k](self.state, jnp.asarray(q))
            self.search_stats["exact"] += 1
        self.dispatches += 1
        scores, out_ids = self._fetch(scores[:B], ids[:B])
        if self.tiered:
            scores, out_ids = self._tier_translate(scores, out_ids)
        if self._quantized and self._masters:
            scores, out_ids = self._master_rerank(queries, scores, out_ids)
        return scores[:, :k], out_ids[:, :k]

    def _tier_translate(self, scores: np.ndarray, ids: np.ndarray):
        """Search ran over device SLOTS; map winners back to global ids.
        Slots that are empty (never occupied, or spilled — their device
        rows are stale) mask to (-inf, -1) and re-sort to the tail."""
        scores, ids = scores.copy(), ids.copy()
        valid = ids >= 0
        gids = np.full_like(ids, -1)
        gids[valid] = self._slot_id[ids[valid]]
        scores[gids < 0] = -np.inf
        order = np.argsort(-scores, axis=1, kind="stable")
        return (np.take_along_axis(scores, order, 1),
                np.take_along_axis(gids, order, 1))

    def _master_rerank(self, queries: np.ndarray, scores: np.ndarray,
                       ids: np.ndarray):
        """int8 final-score repair: winners that still have an fp32 master
        copy (pushed by update, not since touched by lazy_grad) re-score
        against it — exact where exactness exists — then rows re-sort."""
        scores, ids = scores.copy(), ids.copy()
        for b in range(scores.shape[0]):
            hit = False
            for j in range(scores.shape[1]):
                m = self._masters.get(int(ids[b, j]))
                if m is not None:
                    scores[b, j] = float(queries[b] @ m)
                    hit = True
            if hit:
                order = np.argsort(-scores[b], kind="stable")
                scores[b] = scores[b][order]
                ids[b] = ids[b][order]
        return scores, ids

    def _ivf_search(self, q: np.ndarray, k: int, idx):
        """Two-stage search against the clustered snapshot; one jitted
        program per (k, nprobe) — index arrays are traced args, so a
        rebuild with the same shapes reuses the compiled program. The
        sharded backend routes through the hierarchical per-shard merge
        (``sharded_kb_nn_search_ivf``); dense/pallas through the
        single-index two-stage search. Every impl takes the index's
        per-bucket occupancy (``occ``) as a traced arg — the Pallas paths
        use it to walk only each bucket's occupied chunks (skew-proofing,
        see ``repro.kernels.nn_search_ivf``); the jnp/sharded oracles
        ignore it."""
        nprobe = min(self.ann_nprobe, idx.nlist)
        fn = self._ivf_fns.get((k, nprobe))
        if fn is None:
            if isinstance(self.backend, ShardedBackend):
                bk = self.backend
                if self.storage == "int8":
                    def kb_nn_ivf(tbl, c, pc, ps, po, pi, occ, q):
                        return bk.nn_search_ivf_q(tbl, c, pc, ps, po, pi, q,
                                                  k, nprobe)
                else:
                    def kb_nn_ivf(tbl, c, pv, pi, occ, q):
                        return bk.nn_search_ivf(tbl, c, pv, pi, q, k, nprobe)
            elif self._quantized:
                if isinstance(self.backend, PallasBackend):
                    from repro.kernels.nn_search_ivf import (
                        ivf_search_quantized_pallas)
                    interpret = self.backend.interpret

                    def kb_nn_ivf(tbl, qs, qo, c, pc, ps, po, pi, occ, q):
                        return ivf_search_quantized_pallas(
                            tbl, qs, qo, c, pc, ps, po, pi, q, k, nprobe,
                            bucket_occ=occ, interpret=interpret)
                else:
                    from repro.kernels.nn_search_ivf import (
                        ivf_search_quantized_jnp)

                    def kb_nn_ivf(tbl, qs, qo, c, pc, ps, po, pi, occ, q):
                        return ivf_search_quantized_jnp(
                            tbl, qs, qo, c, pc, ps, po, pi, q, k, nprobe)
            elif isinstance(self.backend, PallasBackend):
                from repro.kernels.nn_search_ivf import ivf_search_pallas
                interpret = self.backend.interpret

                def kb_nn_ivf(tbl, c, pv, pi, occ, q):
                    return ivf_search_pallas(tbl, c, pv, pi, q, k, nprobe,
                                             bucket_occ=occ,
                                             interpret=interpret)
            else:
                from repro.kernels.nn_search_ivf import ivf_search_jnp

                def kb_nn_ivf(tbl, c, pv, pi, occ, q):
                    return ivf_search_jnp(tbl, c, pv, pi, q, k, nprobe)
            fn = self._ivf_fns[(k, nprobe)] = jax.jit(kb_nn_ivf)
        occ = idx.bucket_occ
        if self._quantized:
            return fn(self.state.table, self._qscale, self._qoffset,
                      idx.centroids, idx.packed_codes, idx.packed_scale,
                      idx.packed_offset, idx.packed_ids, occ,
                      jnp.asarray(q))
        if self.storage == "int8":      # sharded: fp32 live table,
            return fn(self.state.table,  # quantized sub-index snapshot
                      idx.centroids, idx.packed_codes, idx.packed_scale,
                      idx.packed_offset, idx.packed_ids, occ,
                      jnp.asarray(q))
        return fn(self.state.table, idx.centroids, idx.packed_vecs,
                  idx.packed_ids, occ, jnp.asarray(q))

    # -- ANN index lifecycle (built off the serving path; see ann_index) ---

    @property
    def ann_staleness_rows(self) -> float:
        """Rows written since the current index was built (inf if none).
        On the sharded backend this is the WORST shard's staleness — the
        value the exact-fallback budget gates on, so one hot shard past
        budget degrades the whole bank to exact search until its sub-index
        rebuilds."""
        if self.ann_index is None:
            return float("inf")
        return int((self.shard_write_rows - self._ann_shard_built_at).max())

    @property
    def ann_shard_staleness_rows(self) -> np.ndarray:
        """Per-shard rows written since each sub-index was built (length
        ``ann_shards``; +inf everywhere when no index exists). The
        refresher's per-shard rebuild trigger."""
        if self.ann_index is None:
            return np.full((self.ann_shards,), np.inf)
        return (self.shard_write_rows - self._ann_shard_built_at).astype(
            np.float64)

    def set_ann_index(self, index, *, built_at_writes=None,
                      built_at_shard_writes=None) -> None:
        """Publish a freshly-built index (refresher thread). Index first,
        built_at second: a concurrent reader pairing the OLD index with the
        NEW counter would understate staleness and serve past the budget;
        this order can only overstate it (spurious, safe exact fallback).
        ``built_at_shard_writes``: per-shard snapshot of
        ``shard_write_rows`` taken BEFORE the build read the table (what
        ``rebuild_ann_index`` passes — writes racing the build then count
        as staleness against the new index). ``built_at_writes`` is the
        scalar form: the ``total_write_rows`` value at build time; on a
        sharded engine the global delta since then cannot be attributed
        per shard, so it is charged to EVERY shard — overstating
        staleness, which only triggers spurious (safe) fallback/rebuilds.
        With neither given, the index is treated as fresh as of NOW;
        callers that snapshotted the table earlier must pass clocks."""
        if built_at_shard_writes is None:
            if built_at_writes is not None:
                delta = max(0, self.total_write_rows - int(built_at_writes))
                built_at_shard_writes = self.shard_write_rows - delta
            else:
                built_at_shard_writes = self.shard_write_rows.copy()
        self.ann_index = index
        self._ann_shard_built_at = np.asarray(built_at_shard_writes,
                                              np.int64)

    def rebuild_ann_index(self, *, iters: int = 8,
                          shards: Optional[list] = None) -> int:
        """Snapshot -> cluster -> pack -> swap. Safe to call from a
        background thread: the snapshot is an on-device copy of the table
        made under ``swap_lock`` (so no donating op can delete the table
        mid-read) and moved to the host outside it; the final swap is an
        atomic attribute store; everything between runs on this thread.

        ``shards`` (sharded backend only): rebuild just those shards'
        sub-indexes, keeping every other sub-index — and its staleness
        clock — untouched. A bucket-capacity overflow silently upgrades to
        a full rebuild (detected via the returned index's ``bucket_cap``);
        on the single-index backends ``shards`` is ignored and the whole
        index rebuilds. Returns the number of sub-indexes actually
        re-clustered (the refresher's ``shard_rebuilds`` accounting)."""
        from repro.core.ann_index import (QuantizedIVFIndex,
                                          QuantizedShardedIVFIndex,
                                          ShardedIVFIndex, build_ivf_index,
                                          build_sharded_ivf_index)
        # writes during the build count as stale against the new index
        built_at = self.shard_write_rows.copy()
        with self.swap_lock:
            # cluster on the dequantized snapshot; the packed buckets then
            # re-quantize per-slot (QuantizedIVFIndex), so stage 2 scores
            # int8 rows and never holds an fp32 copy of the bank
            table = (kbm.dequantize_rows(self.state.table, self._qscale,
                                         self._qoffset)
                     if self._quantized else jnp.copy(self.state.table))
        table = np.asarray(table, np.float32)
        put = (self.backend.put_rows if self.ann_shards > 1
               else jnp.asarray)
        wrap = ((lambda ix: ix) if self.storage != "int8" else
                (lambda ix: (QuantizedShardedIVFIndex(ix, put=put)
                             if isinstance(ix, ShardedIVFIndex)
                             else QuantizedIVFIndex(ix))))
        if self.ann_shards == 1:
            index = build_ivf_index(table, nlist=self.ann_nlist,
                                    iters=iters)
            self.set_ann_index(wrap(index), built_at_shard_writes=built_at)
            return 1
        prev = self.ann_index
        base = (prev.base if isinstance(prev, QuantizedShardedIVFIndex)
                else prev if isinstance(prev, ShardedIVFIndex) else None)
        index = build_sharded_ivf_index(table, self.ann_shards,
                                        nlist=self.ann_nlist, iters=iters,
                                        base=base, shards=shards, put=put)
        if index is base:                       # empty shard list: no-op
            return 0
        index = wrap(index)
        if (base is not None and shards is not None
                and index.bucket_cap == base.bucket_cap):
            # partial rebuild: untouched shards keep their old clocks
            new_built = self._ann_shard_built_at.copy()
            rebuilt = sorted({int(s) for s in shards})
            for s in rebuilt:
                new_built[s] = built_at[s]
            built_at = new_built
            self.set_ann_index(index, built_at_shard_writes=built_at)
            return len(rebuilt)
        self.set_ann_index(index, built_at_shard_writes=built_at)
        return self.ann_shards                  # full (re)build

    def warmup(self, max_batch: int = 256) -> None:
        """Pre-compile the lookup/lazy_grad jit buckets up to ``max_batch``
        so serving never stalls on a first-request compile. The ops donate
        their state, so they run on a throwaway copy of it, threaded through
        the calls: the live state is untouched, and the programs compiled
        are the ones served (same shardings, same donation)."""
        st, *qsc = jax.tree.map(jnp.copy, (self.state, self._qscale,
                                           self._qoffset))
        qsc = qsc if self._quantized else []
        grad_fn = self._lazy_fn if self.lazy_update else self._immediate_fn
        b = 8
        top = _bucket(max_batch)
        while b <= top:
            ids = jnp.zeros((b,), jnp.int32)
            zeros = jnp.zeros((b, self.dim), jnp.float32)
            mask = jnp.zeros((b,), jnp.float32)
            _, st, *qsc = self._lookup_fn(st, *qsc, ids)
            st = grad_fn(st, ids, zeros, mask)
            b *= 2

    # -- introspection -----------------------------------------------------

    def table_snapshot(self) -> np.ndarray:
        """Host copy of the live table, always (num_entries, D) fp32-view:
        int8 engines dequantize; tiered engines materialize the full id
        space (resident slots + cold-store rows; never-touched rows read
        as zeros). NOT flushed first: rows with pending lazy gradients
        read as last-applied values (the server's ``table_snapshot``
        barriers behind queued writes; flushing is still the caller's
        choice)."""
        if self._quantized:
            tbl = np.asarray(kbm.dequantize_rows(
                self.state.table, self._qscale, self._qoffset), np.float32)
        else:
            tbl = np.asarray(self.state.table)
        if not self.tiered:
            return tbl
        out = np.zeros((self.num_entries, self.dim), tbl.dtype)
        res = np.flatnonzero(self._slot_id >= 0)
        out[self._slot_id[res]] = tbl[res]
        for g in self.cold_store.ids():
            if self._slot_of[g] < 0:
                rec = self.cold_store.get(g)
                if self._quantized:
                    out[g] = (rec["table"].astype(np.float32)
                              * float(rec["scale"]) + float(rec["offset"]))
                else:
                    out[g] = rec["table"]
        return out

    # every per-row leaf a row owns, in one canonical order — the contract
    # behind replica warm-fill and resharding row streams (kb_router):
    # export -> wire -> import must round-trip bit-identically, including
    # gradients still waiting in the lazy cache and the clip EMA
    ROW_LEAVES = ("table", "version", "grad_sum", "grad_cnt",
                  "grad_sqnorm", "norm_ema")

    def export_rows(self, ids) -> dict:
        """Full per-row state for ``ids`` as ``{leaf: np.ndarray}`` —
        ``ROW_LEAVES`` plus ``scale``/``offset`` side-cars on int8
        engines. Values are raw (int8 codes stay int8 codes), so
        ``import_rows`` on a same-config engine reproduces the rows
        BIT-identically — pending lazy gradients and the norm EMA travel
        too, unlike ``table_snapshot`` which only sees applied values.
        Tiered and sharded engines refuse: their row state is not a flat
        per-id device slice (cold records / owner-masked shards)."""
        if self.tiered:
            raise ValueError("export_rows: tiered engines hold row state "
                             "across device slots + the cold store; "
                             "row-range export is not supported")
        if isinstance(self.backend, ShardedBackend):
            raise ValueError("export_rows: sharded backends are not "
                             "supported (owner-masked row state)")
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        if ids.size and (ids.min() < 0 or ids.max() >= self.num_entries):
            raise ValueError(f"export_rows: ids out of range "
                             f"(0..{self.num_entries - 1})")
        idx = jnp.asarray(ids)
        st = self.state
        out = {leaf: np.asarray(getattr(st, leaf)[idx])
               for leaf in self.ROW_LEAVES}
        if self._quantized:
            out["scale"] = np.asarray(self._qscale[idx])
            out["offset"] = np.asarray(self._qoffset[idx])
        return out

    def import_rows(self, ids, leaves: dict) -> None:
        """Scatter ``export_rows`` output into this engine's rows —
        the receiving half of replica warm-fill and reshard streaming.
        Geometry/storage must match the exporter (leaf set is checked).
        Imported rows count as writes (ANN staleness, spill clocks) and
        drop any fp32 master copies for the touched ids — the master was
        exact for the OLD row value."""
        if self.tiered:
            raise ValueError("import_rows: tiered engines not supported")
        if isinstance(self.backend, ShardedBackend):
            raise ValueError("import_rows: sharded backends not supported")
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        want = set(self.ROW_LEAVES) | (
            {"scale", "offset"} if self._quantized else set())
        if set(leaves) != want:
            raise ValueError(f"import_rows: leaf set {sorted(leaves)} != "
                             f"expected {sorted(want)} (storage mismatch?)")
        if ids.size == 0:
            return
        if ids.min() < 0 or ids.max() >= self.num_entries:
            raise ValueError(f"import_rows: ids out of range "
                             f"(0..{self.num_entries - 1})")
        idx = jnp.asarray(ids)
        st = self.state
        self.state = st._replace(**{
            leaf: getattr(st, leaf).at[idx].set(
                jnp.asarray(leaves[leaf], getattr(st, leaf).dtype))
            for leaf in self.ROW_LEAVES})
        if self._quantized:
            self._qscale = self._qscale.at[idx].set(
                jnp.asarray(leaves["scale"], jnp.float32))
            self._qoffset = self._qoffset.at[idx].set(
                jnp.asarray(leaves["offset"], jnp.float32))
            if self._masters:
                for g in np.unique(ids):
                    self._masters.pop(int(g), None)
        self._count_writes(ids.astype(np.int32))

    def version_snapshot(self) -> np.ndarray:
        """Host copy of per-row version counters (bumped once per touched
        row per applying call — the coalescing-visibility invariant).
        Tiered engines splice cold-store versions into the full id space."""
        if not self.tiered:
            return np.asarray(self.state.version)
        out = np.zeros((self.num_entries,), np.int32)
        ver = np.asarray(self.state.version)
        res = np.flatnonzero(self._slot_id >= 0)
        out[self._slot_id[res]] = ver[res]
        for g in self.cold_store.ids():
            if self._slot_of[g] < 0:
                out[g] = int(self.cold_store.get(g)["version"])
        return out

    def storage_stats(self) -> dict:
        """Memory-residency accounting for the serving tier: what one row
        costs device-side (``bytes_per_row``: D codes + 8 B of scale/offset
        side-car in int8 mode, D * itemsize in fp32) and what the bank
        holds resident right now (table slots + fp32 masters). The router
        sums ``bytes_resident``/row counts across partitions and recomputes
        a weighted ``bytes_per_row``."""
        itemsize = np.dtype(self.state.table.dtype).itemsize
        bpr = self.dim * itemsize + (8 if self._quantized else 0)
        resident = int(self.state.table.shape[0])
        master_bytes = sum(m.nbytes for m in self._masters.values())
        return {
            "mode": self.storage,
            "bytes_per_row": int(bpr),
            "resident_rows": resident,
            "total_rows": int(self.num_entries),
            "cold_rows": len(self.cold_store) if self.tiered else 0,
            "bytes_resident": int(bpr * resident + master_bytes),
            "master_rows": len(self._masters),
            "tier_faults": int(self.tier_faults),
            "tier_spills": int(self.tier_spills),
        }
