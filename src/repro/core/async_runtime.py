"""Asynchronous host runtime: trainer / knowledge-maker concurrency over a
request-coalescing Knowledge-Bank server.

This is the execution model of the paper's Figure 1 on one host, rebuilt on
the pluggable KB engine (``repro.core.kb_engine``):

- ``KnowledgeBankServer``: the stand-in for the sharded DynamicEmbedding /
  Bigtable servers. Concurrent trainer/maker calls do NOT each pay a locked
  device round-trip: every call enqueues an (op, ids, payload) future, and a
  dispatcher thread drains the queue and executes ONE jitted batched op per
  maximal FIFO run of same-op requests. N concurrent clients cost one device
  dispatch — the RPC-amortization trick CARLS' DynamicEmbedding servers and
  TF-GNN's bulk graph services use, in-process. Set ``coalesce=False`` for
  the per-call locked baseline (kept as the benchmark ablation). The
  server's whole client surface is also a versioned wire protocol
  (``repro.core.kb_protocol`` / ``kb_transport``): remote processes'
  requests enter the same queue via ``enqueue_op``, so they coalesce with
  in-process callers', and everything here takes the ``KBClient``
  duck-type — a ``RemoteKnowledgeBank`` drops in wherever the concrete
  server does.
- ``MakerRuntime`` + ``MakerJob``: the paper's knowledge makers as
  independently-paced background engine clients — the same
  load-latest-checkpoint / compute / push loop the ``IVFRefresher`` index
  maker runs, generalized over the four maker types (``embedding_refresh``,
  ``label_mining``, ``graph_agreement``, ``graph_builder``). Every job tags
  its writes with the checkpoint step it loaded, so staleness is measurable
  PER MAKER (``ckpt_version_lag``); per-job counters (``maker_steps``,
  ``rows_written``) surface through ``KnowledgeBankServer.maker_stats``.
  Label/graph knowledge lands in a lock-protected ``SharedFeatureStore``.
- ``run_async_training``: the trainer loop. Each step it (1) looks up
  neighbor features + embeddings from the server, (2) runs the jitted train
  core, (3) hands the neighbor-embedding gradients back to the server's lazy
  cache, (4) periodically publishes a checkpoint.

Why coalescing is legal: the engine's batched ops are deterministic under
duplicate ids, version counters bump once per touched row per call, and a
client blocks on its future before issuing its next request — so per-client
program order is preserved. nn_search coalescing additionally relies on the
search being a pure function of (engine state, ANN index, queries) — true
for exact, single-index IVF, AND the sharded hierarchical IVF merge — which
is why only same-(k, mode) runs merge: the compiled program and the index
snapshot they observe are then identical for every merged request. A
merged run is equivalent to a serial interleaving of its requests for
lookup / update / flush / nn_search, and
for lazy_grad with entry-side clipping off (cache adds commute). With
entry-side clipping ON (zmax > 0), a merged lazy_grad run clips every
contribution against the pre-drain norm EMA and advances the EMA one step
on the pooled mean — same-row contributions from different clients are
treated as one unordered batch rather than two sequenced ones. That is the
paper's own model (§3.2 caches trainer gradients with no ordering
guarantee); the clip cap differs from a serial schedule only in the decay
weighting of one EMA step, never in which gradients are cached.

Asynchrony knobs: number of maker threads, maker batch size, checkpoint
publish period (== the paper's "data freshness" axis, measured and reported
as `staleness` = trainer_step - ckpt_step_used_by_maker), and the KB engine
backend (dense | sharded | pallas).
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import MemoryCheckpointStore
from repro.core.kb_engine import KBEngine
from repro.core.kb_protocol import KBClient
from repro.core.knowledge_bank import (feature_store_create, fs_update_labels,
                                       fs_update_neighbors)
from repro.core.knowledge_maker import vote_agreement_labels
from repro.core.trainer import make_async_train_fns
from repro.data.pipeline import SyntheticGraphCorpus
from repro.models.model import LM
from repro.optim import AdamW
from repro.sharding.partition import DistContext


class KBServerClosedError(RuntimeError):
    """Raised by requests submitted after ``KnowledgeBankServer.close()``
    began — fail fast instead of hanging in ``_Request.wait()`` behind a
    dispatcher that is (or has finished) draining."""


class _Request:
    """One queued client call; ``event`` fires when ``result`` is ready.
    ``meta`` carries the op's step tag (lookup: trainer_step; update:
    src_step) so staleness accounting happens in execution order."""

    __slots__ = ("op", "ids", "payload", "k", "mode", "excl", "shape",
                 "meta", "event", "result", "error", "_callbacks",
                 "t_submit")

    def __init__(self, op, ids=None, payload=None, k=None, mode=None,
                 excl=None, shape=None, meta=0):
        self.op, self.ids, self.payload, self.k = op, ids, payload, k
        self.mode, self.excl, self.shape, self.meta = mode, excl, shape, meta
        self.event = threading.Event()
        self.result = None
        self.error = None
        self._callbacks: list = []
        self.t_submit = 0.0         # host clock at submission

    def wait(self):
        self.event.wait()
        if self.error is not None:
            raise self.error
        return self.result

    def add_done_callback(self, fn) -> None:
        """Run ``fn(self)`` once ``result``/``error`` is set — immediately
        if it already is. The wire transport's out-of-order completion
        hook (protocol v4): the connection queues the response frame the
        moment the dispatcher finishes THIS request instead of parking a
        thread in ``wait()`` per in-flight wire request. Callbacks run on
        the completing thread (the dispatcher) and must be cheap and
        non-blocking. Each registered callback fires exactly once."""
        self._callbacks.append(fn)
        if self.event.is_set():
            self._fire_callbacks()

    def _fire_callbacks(self) -> None:
        # list.pop is atomic under the GIL: when registration races
        # completion, each callback is popped (hence fired) exactly once,
        # by whichever side wins. Never lets a callback error escape into
        # the dispatcher (deliver, don't kill).
        while self._callbacks:
            try:
                cb = self._callbacks.pop()
            except IndexError:
                return
            try:
                cb(self)
            except Exception:
                pass


def _mergeable(prev: _Request, r: _Request) -> bool:
    """Can ``r`` join the run started by ``prev`` as one batched op?"""
    if prev.op != r.op:
        return False
    if r.op in ("lookup", "update", "lazy_grad"):
        return True
    if r.op != "nn" or prev.k != r.k or prev.mode != r.mode:
        return False
    # exclusion lists concatenate row-aligned with the queries, so merged
    # requests must agree on the per-query exclusion width (incl. "none")
    pw = None if prev.excl is None else prev.excl.shape[1]
    rw = None if r.excl is None else r.excl.shape[1]
    return pw == rw


def _commutes(a: _Request, b: _Request) -> bool:
    """May ``a`` execute before ``b`` even though ``b`` was queued first?
    The legality table behind cross-op reordering (``reorder=True``):

    - lookup/lookup: always. Lookups mutate (they apply pending lazy
      gradients) but the application is idempotent per row — whichever
      lookup runs first applies and clears the pending cache, and both
      observe the same post-apply rows either way.
    - lazy_grad/lazy_grad: always — cache adds commute (the one EMA-
      weighting caveat is identical to merging them, see module docstring).
    - nn/nn: always — pure functions of (state, index snapshot); index
      refresh timing relative to queue order is already unordered.
    - any other pair within {lookup, update, lazy_grad}: only when the id
      sets are DISJOINT — then neither op observes or clobbers the other's
      rows (update/update last-writer-wins only matters on shared ids;
      lookup's pending-apply and lazy_grad's cache add touch only own ids).
    - flush / barrier / nn-vs-write: never — flush applies EVERY pending
      gradient, a barrier is a consistency point, and nn_search scores
      reflect table rows that any write or pending-apply could move.
    """
    if a.op == b.op and a.op in ("lookup", "lazy_grad", "nn"):
        return True
    if (a.op in ("lookup", "update", "lazy_grad")
            and b.op in ("lookup", "update", "lazy_grad")):
        return not bool(np.isin(a.ids, b.ids).any())
    return False


class KnowledgeBankServer:
    """Thread-safe KB server with request coalescing over a ``KBEngine``.

    Public surface is unchanged from the per-call era (lookup / update /
    lazy_grad / flush / nn_search / table_snapshot + staleness metrics);
    what changed is the execution model — see the module docstring."""

    def __init__(self, num_entries: Optional[int] = None,
                 dim: Optional[int] = None, *,
                 engine: Optional[KBEngine] = None, backend="dense",
                 dist: Optional[DistContext] = None,
                 lazy_lr: float = 0.1, zmax: float = 3.0,
                 lazy_update: bool = True, coalesce: bool = True,
                 coalesce_window_s: float = 0.0, max_coalesce: int = 256,
                 reorder: bool = False, reorder_window: int = 8,
                 search_mode: str = "exact", ann_nlist: int = 64,
                 ann_nprobe: int = 8,
                 ann_stale_rows: Optional[int] = None,
                 storage: str = "fp32", cache_rows: int = 0,
                 resident_rows: Optional[int] = None,
                 cold_after_rows: Optional[int] = None,
                 cold_dir: Optional[str] = None,
                 interpret: Optional[bool] = None):
        if engine is None:
            engine = KBEngine(num_entries, dim, backend=backend, dist=dist,
                              lazy_lr=lazy_lr, zmax=zmax,
                              lazy_update=lazy_update,
                              interpret=interpret,
                              search_mode=search_mode, ann_nlist=ann_nlist,
                              ann_nprobe=ann_nprobe,
                              ann_stale_rows=ann_stale_rows,
                              storage=storage, resident_rows=resident_rows,
                              cold_after_rows=cold_after_rows,
                              cold_dir=cold_dir)
        self.engine = engine
        self._ann_refresher = None
        self._maker_runtime = None
        self.coalesce = coalesce
        self.coalesce_window_s = coalesce_window_s
        self.max_coalesce = max_coalesce
        # cross-op reordering (off by default: FIFO run formation is the
        # bit-exact baseline): a request may hop over up to reorder_window
        # earlier runs it commutes with (see _commutes) to join a mergeable
        # run — interleaved multi-client streams then coalesce into bigger
        # dispatches instead of run-length-1 ping-pong
        self.reorder = reorder
        self.reorder_window = reorder_window
        # row -> trainer step of the checkpoint that produced the row
        self._row_src_step = np.full((engine.num_entries,), -1, np.int64)
        # hot-id LRU in front of the engine (cache_rows = 0 disables).
        # Legal because the engine's lookup is idempotent between writes —
        # a populating lookup already applied (and cleared) the row's
        # pending delta, so replaying it is a pure gather — and every
        # write invalidates the ids it touches (flush clears everything).
        self.cache_rows = cache_rows
        self._row_cache: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self.metrics = {"lookups": 0, "updates": 0, "lazy_grads": 0,
                        "rows_served": 0, "stale_rows_served": 0,
                        "staleness_sum": 0.0,
                        "requests": 0, "dispatches": 0, "max_run": 0,
                        "reorders": 0, "cache_hits": 0, "cache_misses": 0,
                        # host seconds: requests queued (submit to pop),
                        # the dispatcher busy (pop to the batch's last
                        # reply), and inside it the engine calls
                        "queue_wait_s": 0.0, "dispatcher_busy_s": 0.0,
                        "engine_call_s": 0.0}
        self._mlock = threading.Lock()      # metrics + row_src_step
        self._elock = threading.Lock()      # engine state (direct path)
        self._queue: deque = deque()
        self._runs = 0                      # runs executed; tags kb.run
        self._cond = threading.Condition()
        self._closed = False
        self._dispatcher = None
        if coalesce:
            self._dispatcher = threading.Thread(
                target=self._dispatch_loop, daemon=True, name="kb-dispatch")
            self._dispatcher.start()

    # -- client API --------------------------------------------------------

    def lookup(self, ids: np.ndarray, *, trainer_step: int = 0) -> np.ndarray:
        """Fetch rows, applying pending lazy gradients first. Blocking;
        result is identical to a serial execution at this request's queue
        position (merged lookups are deterministic under duplicate ids, so
        slicing a coalesced batch can't change any caller's rows).
        ``trainer_step`` tags the call for staleness accounting."""
        ids = np.asarray(ids)
        return self._submit(_Request("lookup", ids.reshape(-1),
                                     shape=ids.shape, meta=trainer_step))

    def update(self, ids, values, *, src_step: int = 0) -> None:
        """Direct write (maker push); last-writer-wins on duplicate ids —
        within one call AND within a merged run, because requests
        concatenate in FIFO order and the engine dedupes keeping the final
        occurrence. ``src_step`` stamps rows for the staleness metrics and
        charges the rows to the ANN index's (per-shard) staleness clock."""
        ids = np.asarray(ids)
        self._submit(_Request("update", ids.reshape(-1),
                              np.asarray(values).reshape(ids.size, -1),
                              meta=src_step))

    def lazy_grad(self, ids, grads) -> None:
        """Cache gradients for lazy application on next lookup/flush.
        Cache adds commute, so merge order is unobservable (with entry-side
        clipping on, see the module docstring for the one EMA-weighting
        caveat). Counts toward ANN staleness immediately — the write WILL
        reach the table."""
        ids = np.asarray(ids)
        self._submit(_Request("lazy_grad", ids.reshape(-1),
                              np.asarray(grads, np.float32).reshape(
                                  ids.size, -1)))

    def flush(self) -> None:
        """Apply every pending cached gradient now (expiration path)."""
        self._submit(_Request("flush"))

    def nn_search(self, queries, k: int, *, mode: Optional[str] = None,
                  exclude_ids=None):
        """Top-k MIPS over the bank. ``mode`` overrides the engine's
        ``search_mode`` per request (exact | ivf); only same-(k, mode,
        exclusion-width) searches coalesce, because a merged run must be
        one compiled program observing one index snapshot — that, plus
        the search being a pure function of (state, index, queries) on
        every backend (including the sharded per-shard-sub-index merge),
        makes the merge invisible to callers. ``exclude_ids`` (B, E)
        int32, -1 = no-op, bans rows per query (the engine over-fetches
        k+E through the live path — IVF included — and masks). IVF falls
        back to exact when the index is absent or past its staleness
        budget; returned scores are always live (re-ranked), so staleness
        costs recall only."""
        queries = np.asarray(queries)
        excl = (None if exclude_ids is None
                else np.asarray(exclude_ids,
                                np.int32).reshape(queries.shape[0], -1))
        return self._submit(_Request("nn", payload=queries, k=k, mode=mode,
                                     excl=excl))

    def table_snapshot(self) -> np.ndarray:
        """Consistent snapshot: barriers behind every queued write first.
        Still legal after a CLEAN close (results summaries read the final
        table): the drain emptied the queue, so the barrier is vacuous and
        the engine is quiescent. During a close still in progress the
        barrier fails fast like any other request."""
        if not (self._closed and self._dispatcher is None):
            self._submit(_Request("barrier"))   # drain queued writes first
        with self._elock:
            return self.engine.table_snapshot()

    def export_rows(self, ids) -> dict:
        """Full per-row engine state for ``ids`` (every leaf, raw dtypes —
        see ``KBEngine.export_rows``). Barriers behind queued writes first,
        like ``table_snapshot``, so the exported rows reflect everything
        acknowledged before this call — the replica warm-fill / resharding
        read primitive."""
        if not (self._closed and self._dispatcher is None):
            self._submit(_Request("barrier"))
        with self._elock:
            return self.engine.export_rows(ids)

    def import_rows(self, ids, leaves: dict) -> None:
        """Scatter previously-exported rows into the engine (standby fill,
        reshard landing) — bit-identical round trip. Runs behind a barrier
        and under the engine lock like any write; touched ids leave the
        hot-id cache (imported values supersede cached ones)."""
        if not (self._closed and self._dispatcher is None):
            self._submit(_Request("barrier"))
        with self._elock:
            self.engine.import_rows(ids, leaves)
            self._invalidate_cache(np.asarray(ids).reshape(-1))

    def stats(self) -> dict:
        """Everything a remote operator can ask in one call — the payload
        of the wire protocol's ``StatsRequest`` (flat numbers / strings /
        sub-dicts only, so it serializes pickle-free): server metrics, the
        derived staleness/coalescing ratios, the engine's search counters,
        and any attached maker fleet's per-maker counters."""
        with self._mlock:
            m = dict(self.metrics)
        storage = self.engine.storage_stats()
        # tier counters are engine-side cumulative totals; mirroring them
        # into metrics lets the router's generic numeric summing aggregate
        # them across partitions like any other counter
        m["tier_faults"] = storage["tier_faults"]
        m["tier_spills"] = storage["tier_spills"]
        m["engine_op_s"] = self.engine.op_s
        m["engine_args_s"] = self.engine.args_s
        m["engine_wait_s"] = self.engine.wait_s
        m["donation_misses"] = self.engine.donation_misses
        return {
            "metrics": m,
            "mean_staleness": float(self.mean_staleness),
            "coalescing_factor": float(self.coalescing_factor),
            "search_stats": dict(self.engine.search_stats),
            "backend": self.engine.backend.name,
            "num_entries": int(self.engine.num_entries),
            "dim": int(self.engine.dim),
            "storage": storage,
            "maker_stats": self.maker_stats,
        }

    @property
    def num_entries(self) -> int:
        """Bank geometry, mirrored from the engine — part of the client
        duck-type (``RemoteKnowledgeBank`` learns these from the wire
        handshake instead)."""
        return self.engine.num_entries

    @property
    def dim(self) -> int:
        return self.engine.dim

    def warmup(self, max_batch: int = 256) -> None:
        """Pre-compile the engine's jit buckets up to ``max_batch``."""
        with self._elock:
            self.engine.warmup(max_batch)

    @property
    def mean_staleness(self) -> float:
        served = max(self.metrics["rows_served"], 1)
        return self.metrics["staleness_sum"] / served

    @property
    def coalescing_factor(self) -> float:
        """Mean requests per device dispatch (1.0 = no coalescing won)."""
        return self.metrics["requests"] / max(self.metrics["dispatches"], 1)

    def attach_maker_runtime(self, runtime) -> None:
        """Register the ``MakerRuntime`` serving this bank so operators can
        read per-maker counters from the server they already monitor
        (``maker_stats``). Observability-only: the runtime's lifecycle
        (start/stop) stays with its owner."""
        self._maker_runtime = runtime

    @property
    def maker_stats(self) -> Dict[str, Dict]:
        """Per-maker ``{name: {maker_steps, rows_written, ckpt_version_lag,
        ...}}`` from the attached ``MakerRuntime`` (empty when none)."""
        if self._maker_runtime is None:
            return {}
        return self._maker_runtime.stats()

    def start_ann_refresher(self, **kwargs):
        """Register the IVF index maker (see repro.core.ann_index): a
        daemon thread that rebuilds the engine's ANN index off the serving
        path — per-shard independently on the sharded backend, so one hot
        shard re-clusters at 1/S of the full build cost. Stopped by
        ``close``. Returns the thread (its ``rebuilds`` /
        ``shard_rebuilds`` counters are the observability hooks)."""
        from repro.core.ann_index import IVFRefresher
        if self._ann_refresher is None:
            self._ann_refresher = IVFRefresher(self.engine, **kwargs)
            self._ann_refresher.start()
        return self._ann_refresher

    def close(self, timeout_s: float = 60.0) -> None:
        """Stop the dispatcher after draining every already-queued request.
        The moment close() begins, NEW submissions fail fast with
        ``KBServerClosedError`` — they used to race the drain and could
        block forever in ``_Request.wait()`` on a queue nobody would ever
        service again. Raises if the drain does not finish within
        ``timeout_s``; requests still stranded in the queue at that point
        are failed with the same error, never left hanging."""
        if self._ann_refresher is not None:
            self._ann_refresher.stop()
            self._ann_refresher = None
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._dispatcher is None:
            return
        self._dispatcher.join(timeout=timeout_s)
        if self._dispatcher.is_alive():
            with self._cond:
                stranded = list(self._queue)
                self._queue.clear()
            err = KBServerClosedError(
                f"request abandoned: KB dispatcher did not drain within "
                f"{timeout_s}s of close()")
            for r in stranded:
                r.error = err
                r.event.set()
                r._fire_callbacks()
            raise RuntimeError(
                f"KB dispatcher did not drain within {timeout_s}s "
                f"({len(stranded)} stranded requests failed)")
        self._dispatcher = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- execution ---------------------------------------------------------

    def enqueue_op(self, op: str, *, ids=None, payload=None, k=None,
                   mode=None, excl=None, shape=None, meta: int = 0):
        """Queue one client op WITHOUT waiting and return the pending
        request (call ``.wait()`` for the result). This is the transport
        layer's entry point (``repro.core.kb_transport``): a connection
        reader enqueues decoded wire requests here back-to-back, so
        cross-process traffic lands in the same coalescing window as
        in-process callers'. Raises ``KBServerClosedError`` once close()
        has begun."""
        return self._submit_nowait(_Request(op, ids, payload, k=k,
                                            mode=mode, excl=excl,
                                            shape=shape, meta=meta))

    def _submit_nowait(self, req: _Request) -> _Request:
        req.t_submit = time.perf_counter()
        if self.coalesce:
            with self._cond:
                if self._closed:
                    raise KBServerClosedError(
                        "KnowledgeBankServer is closed — request submitted "
                        "after close() began")
                if req.op != "barrier":     # barriers never dispatch; keep
                    with self._mlock:       # coalescing_factor honest
                        self.metrics["requests"] += 1
                self._queue.append(req)
                self._cond.notify()
            return req
        # per-call locked baseline (coalesce=False)
        if self._closed:
            raise KBServerClosedError(
                "KnowledgeBankServer is closed — request submitted after "
                "close() began")
        if req.op != "barrier":
            with self._mlock:
                self.metrics["requests"] += 1
        with self._elock:
            t = time.perf_counter()
            self._execute_run([req])
            busy = time.perf_counter() - t
        with self._mlock:
            self.metrics["dispatcher_busy_s"] += busy
        return req

    def _submit(self, req: _Request):
        return self._submit_nowait(req).wait()

    def _dispatch_loop(self):
        while True:
            with jax.profiler.TraceAnnotation("kb.dispatch.wait"):
                with self._cond:
                    while not self._queue and not self._closed:
                        self._cond.wait()
                    if self._closed and not self._queue:
                        return
                if self.coalesce_window_s:
                    time.sleep(self.coalesce_window_s)  # let the queue fill
            with jax.profiler.TraceAnnotation("kb.dispatch.form"):
                with self._cond:
                    t_pop = time.perf_counter()
                    batch = [self._queue.popleft()
                             for _ in range(min(len(self._queue),
                                                self.max_coalesce))]
                runs = self._form_runs(batch)
            for run in runs:
                with self._elock:
                    self._execute_run(run)
            busy = time.perf_counter() - t_pop
            waited = sum(t_pop - r.t_submit for r in batch
                         if r.op != "barrier")
            with self._mlock:
                self.metrics["queue_wait_s"] += waited
                self.metrics["dispatcher_busy_s"] += busy

    def _form_runs(self, batch: List[_Request]) -> List[List[_Request]]:
        """Group a popped batch into runs, each one batched device dispatch.

        FIFO mode (default): maximal runs of consecutive same-op requests —
        execution order IS queue order. With ``reorder=True`` a request
        that can't extend the tail run may instead hop backwards over up to
        ``reorder_window`` earlier runs and join the nearest mergeable one,
        PROVIDED it commutes with every request it crosses (``_commutes``).
        Hoisting is legal exactly then: the reordered schedule is a series
        of transpositions of commuting pairs away from FIFO, and joining a
        run is the ordinary coalescing merge — so results are bit-identical
        to the FIFO schedule (tests/test_kb_router.py proves it property-
        style, reorder-on vs reorder-off). Per-client program order is
        safe for pipelined clients too: their in-flight requests reorder
        only when the id sets are disjoint, where order is unobservable."""
        runs: List[List[_Request]] = []
        hoisted = 0
        for r in batch:
            if runs and _mergeable(runs[-1][0], r):
                runs[-1].append(r)
                continue
            if self.reorder and runs:
                target = None
                i = len(runs) - 1
                hops = 0
                while i >= 0 and hops < self.reorder_window:
                    if not all(_commutes(r, q) for q in runs[i]):
                        break
                    i -= 1
                    hops += 1
                    if i >= 0 and _mergeable(runs[i][0], r):
                        target = i
                        break
                if target is not None:
                    runs[target].append(r)
                    hoisted += 1
                    continue
            runs.append([r])
        if hoisted:
            with self._mlock:
                self.metrics["reorders"] += hoisted
        return runs

    def _execute_run(self, run: List[_Request]):
        """One batched engine call for ``run``, inside a ``kb.run`` span
        whose children split the host work around the call: ``kb.run.args``
        (concatenating the requests) and ``kb.run.reply`` (slicing results,
        accounting, waking the callers)."""
        op, seq = run[0].op, self._runs
        self._runs += 1
        n_ids = sum(r.ids.size for r in run if r.ids is not None)
        with jax.profiler.TraceAnnotation("kb.run", op=op, run=seq,
                                          n_req=len(run), n_ids=n_ids):
            args = out = error = None
            try:
                with jax.profiler.TraceAnnotation("kb.run.args"):
                    fn, args, kwargs = self._run_call(run)
                if fn is not None:
                    out = self._call_engine(seq, fn, args, kwargs)
            except Exception as e:      # deliver, don't kill the dispatcher
                error = e
            finally:
                with jax.profiler.TraceAnnotation("kb.run.reply"):
                    try:
                        if error is None:
                            self._reply(run, args, out)
                    except Exception as e:
                        error = e
                    finally:
                        for r in run:
                            if error is not None:
                                r.error = error
                            r.event.set()
                            r._fire_callbacks()

    def _run_call(self, run: List[_Request]):
        """(engine method, args, kwargs) of a run's one engine call; the
        method is None for a barrier."""
        op, eng = run[0].op, self.engine
        if op == "lookup":
            fn = self._cached_lookup if self.cache_rows > 0 else eng.lookup
            return fn, (np.concatenate([r.ids for r in run]),), {}
        if op in ("update", "lazy_grad"):
            return getattr(eng, op), (np.concatenate([r.ids for r in run]),
                                      np.concatenate([r.payload
                                                      for r in run])), {}
        if op == "flush":
            return eng.flush, (), {}
        if op == "nn":
            excl = (None if run[0].excl is None
                    else np.concatenate([r.excl for r in run]))
            return eng.nn_search, (np.concatenate([r.payload for r in run]),
                                   run[0].k), {"mode": run[0].mode,
                                               "exclude_ids": excl}
        return None, (), {}

    def _call_engine(self, seq: int, fn, args, kwargs):
        """Call the engine for run ``seq``; its host seconds go to
        ``engine_call_s`` and its device calls to ``dispatches``."""
        eng = self.engine
        before = eng.dispatches
        eng.current_run = seq
        t = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            dt = time.perf_counter() - t
            eng.current_run = -1
            with self._mlock:
                self.metrics["engine_call_s"] += dt
                self.metrics["dispatches"] += eng.dispatches - before

    def _reply(self, run: List[_Request], args: tuple, out) -> None:
        """Hand each request its slice of the run's result ``out`` and
        account the run (staleness, op counters, the write-invalidated
        cache); ``args`` are the engine call's arguments."""
        op = run[0].op
        if op == "lookup":
            off = 0
            for r in run:
                n = r.ids.size
                r.result = out[off:off + n].reshape(*r.shape, -1)
                off += n
            # staleness is accounted HERE, in execution order, so a
            # concurrent maker update landing after this run cannot
            # retag rows this lookup served from the older checkpoint
            with self._mlock:
                for r in run:
                    src = self._row_src_step[r.ids]
                    known = src >= 0
                    self.metrics["lookups"] += 1
                    self.metrics["rows_served"] += r.ids.size
                    self.metrics["stale_rows_served"] += int(
                        (known & (src < r.meta)).sum())
                    self.metrics["staleness_sum"] += float(
                        np.maximum(r.meta - src[known], 0).sum())
        elif op == "update":
            self._invalidate_cache(args[0])
            with self._mlock:
                for r in run:
                    self._row_src_step[r.ids] = r.meta
                    self.metrics["updates"] += 1
        elif op == "lazy_grad":
            self._invalidate_cache(args[0])
            with self._mlock:
                self.metrics["lazy_grads"] += len(run)
        elif op == "flush":
            self._row_cache.clear()
        elif op == "nn":
            scores, ids = out
            off = 0
            for r in run:
                n = r.payload.shape[0]
                r.result = (scores[off:off + n], ids[off:off + n])
                off += n
        with self._mlock:
            self.metrics["max_run"] = max(self.metrics["max_run"], len(run))

    def _cached_lookup(self, ids: np.ndarray) -> np.ndarray:
        """Hot-id LRU read path (see __init__): serve repeats from host
        RAM, engine-lookup only the distinct missing ids, refresh the
        cache with what came back. Runs under ``_elock`` like every other
        engine touch. A cache hit on a tiered engine also skips a
        redundant fault-in — the cached value IS what the fault would
        reconstruct (spill/restore is bit-identical)."""
        flat = ids.reshape(-1)
        out = np.empty((flat.size, self.engine.dim), np.float32)
        cache = self._row_cache
        miss_pos = []
        hits = 0
        for i in range(flat.size):
            row = cache.get(int(flat[i]))
            if row is None:
                miss_pos.append(i)
            else:
                cache.move_to_end(int(flat[i]))
                out[i] = row
                hits += 1
        if miss_pos:
            uniq, inv = np.unique(flat[miss_pos], return_inverse=True)
            vals = self.engine.lookup(uniq)
            out[miss_pos] = vals[inv]
            for j in range(uniq.size):
                cache[int(uniq[j])] = vals[j]
            while len(cache) > self.cache_rows:
                cache.popitem(last=False)
        with self._mlock:
            self.metrics["cache_hits"] += hits
            self.metrics["cache_misses"] += len(miss_pos)
        return out

    def _invalidate_cache(self, ids: np.ndarray) -> None:
        """Drop written rows from the hot-id cache (the legality half of
        the caching contract)."""
        if self._row_cache:
            for g in np.unique(ids):
                self._row_cache.pop(int(g), None)


class SharedFeatureStore:
    """Host-side ``FeatureStore`` shared by concurrent maker jobs.

    The functional fs ops stay the single source of label/graph semantics
    (confidence gating lives in ``fs_update_labels``); this wrapper adds
    the one thing threads need — a lock around each read-modify-write —
    and returns write counts so makers can report ``rows_written``
    honestly (a gate-rejected label is not a write)."""

    def __init__(self, num_entries: int, max_neighbors: int = 8):
        self._lock = threading.Lock()
        self.fs = feature_store_create(num_entries, max_neighbors)

    def snapshot(self):
        with self._lock:
            return self.fs

    def labels(self) -> np.ndarray:
        with self._lock:
            return np.asarray(self.fs.labels)

    def labeled_ids(self, cap: Optional[int] = None) -> np.ndarray:
        """Currently-labeled node ids; ``cap`` takes an evenly-strided
        subsample so callers see a bounded batch size."""
        lab = np.flatnonzero(self.labels() >= 0)
        if cap is not None and lab.size > cap:
            lab = lab[np.linspace(0, lab.size - 1, cap).astype(np.int64)]
        return lab

    def update_labels(self, ids, labels, conf) -> int:
        """Confidence-gated label write; returns how many labels the gate
        actually accepted."""
        ids = np.asarray(ids)
        conf = np.asarray(conf)
        with self._lock:
            accepted = int(
                (conf > np.asarray(self.fs.label_conf)[ids]).sum())
            self.fs = fs_update_labels(self.fs, jnp.asarray(ids),
                                       jnp.asarray(labels),
                                       jnp.asarray(conf))
            return accepted

    def update_neighbors(self, ids, nbr_ids, nbr_weights) -> int:
        ids = np.asarray(ids)
        nbr_ids = np.asarray(nbr_ids)
        nbr_weights = np.asarray(nbr_weights, np.float32)
        width = int(self.fs.nbr_ids.shape[1])
        if nbr_ids.shape[1] > width:
            raise ValueError(f"{nbr_ids.shape[1]} neighbors per node won't "
                             f"fit this store's width {width}")
        if nbr_ids.shape[1] < width:    # narrower writers pad with the
            pad = width - nbr_ids.shape[1]          # store's missing marker
            nbr_ids = np.concatenate(
                [nbr_ids, np.full((len(ids), pad), -1, nbr_ids.dtype)], 1)
            nbr_weights = np.concatenate(
                [nbr_weights, np.zeros((len(ids), pad), np.float32)], 1)
        with self._lock:
            self.fs = fs_update_neighbors(self.fs, jnp.asarray(ids),
                                          jnp.asarray(nbr_ids),
                                          jnp.asarray(nbr_weights))
            return int(ids.size)


class MakerJob(threading.Thread):
    """One independently-paced knowledge maker (the ``IVFRefresher``
    pattern generalized): load the latest trainer checkpoint, compute one
    batch of knowledge over a round-robin slice of nodes, push it through
    the coalescing server, repeat.

    Every push is tagged with the checkpoint step the job loaded
    (``src_step``), so the server's staleness accounting — and this job's
    own ``ckpt_version_lag`` counters — measure data freshness per maker.
    A failing step records ``last_error`` and keeps the thread alive
    (a silently-dead maker would freeze its knowledge at the last write,
    exactly like a dead index refresher)."""

    def __init__(self, runtime: "MakerRuntime", name: str, kind: str,
                 step_fn: Callable, nodes: np.ndarray, *,
                 batch_size: int = 64, min_period_s: float = 0.0,
                 needs_ckpt: bool = True):
        super().__init__(daemon=True, name=name)
        self.runtime, self.kind, self.step_fn = runtime, kind, step_fn
        self.nodes = np.asarray(nodes)
        self.batch_size = batch_size
        self.min_period_s = min_period_s
        self.needs_ckpt = needs_ckpt
        self.stop_event = threading.Event()
        self.steps = 0
        self.rows_written = 0
        self.lag_sum = 0
        self.last_lag = 0
        self.errors = 0
        # bounded: long-lived serving makers would otherwise grow this
        # forever; recent history is all tests/diagnostics ever read
        self.ckpt_steps_used: deque = deque(maxlen=4096)
        self.last_error: Optional[BaseException] = None
        self._cursor = 0
        self._ckpt_cache: Optional[tuple] = None    # (step, params)

    def _load_ckpt(self):
        """Latest checkpoint, re-READ only when the published step moved:
        ``latest_step()`` is a cheap probe (dict max / listdir), while a
        full ``load_latest()`` on the disk store re-parses every weight —
        at maker pacing that would be the whole npz per batch."""
        store = self.runtime.ckpts
        latest = store.latest_step()
        if latest is None:
            return None, None
        if self._ckpt_cache is None or self._ckpt_cache[0] != latest:
            self._ckpt_cache = store.load_latest()
        return self._ckpt_cache

    def _next_ids(self) -> np.ndarray:
        ids = self.nodes[np.arange(self._cursor,
                                   self._cursor + self.batch_size)
                         % len(self.nodes)]
        self._cursor = (self._cursor + self.batch_size) % len(self.nodes)
        return ids

    def run(self):
        rt = self.runtime
        # error/idle cycles honor the job's pacing floor too (never
        # faster than the 5ms poll) — a crashing maker must not saturate
        # the server the pacing knob was configured to protect
        backoff = max(self.min_period_s, 0.005)
        while not self.stop_event.is_set():
            try:
                if rt.ckpts is not None:
                    step, params = self._load_ckpt()
                else:
                    step, params = None, None
                if self.needs_ckpt and params is None:
                    self.stop_event.wait(backoff)   # nothing published yet
                    continue
                step = 0 if step is None else int(step)
                ids = self._next_ids()
                rows = self.step_fn(params, step, ids)
                self.last_error = None
            except Exception as e:      # record, back off, stay alive —
                self.last_error = e     # but a crashed batch is NOT a
                self.errors += 1        # maker step: counters must not
                self.stop_event.wait(backoff)   # paint a broken maker
                continue                        # as a productive one
            if rows is None:            # idle: preconditions not met (e.g.
                self.stop_event.wait(backoff)   # no labeled nodes yet) —
                continue                # back off without burning a step
            self.steps += 1
            self.rows_written += int(rows)
            # staleness = trainer's clock minus the checkpoint this batch
            # was computed from — the paper's data-freshness axis, per job
            lag = max(rt.trainer_step - step, 0)
            self.last_lag = lag
            self.lag_sum += lag
            self.ckpt_steps_used.append(step)
            if self.min_period_s:
                self.stop_event.wait(self.min_period_s)

    def stop(self, timeout_s: float = 30.0):
        self.stop_event.set()
        self.join(timeout=timeout_s)


class MakerRuntime:
    """Registry + lifecycle for the paper's knowledge makers, all clients
    of ONE knowledge bank.

    ``server`` is any ``repro.core.kb_protocol.KBClient`` — the concrete
    in-process ``KnowledgeBankServer`` (the zero-copy case) or a
    ``RemoteKnowledgeBank`` connected over the wire — which is what lets
    the SAME runtime run its fleet inside the trainer process or as a
    standalone maker worker (``launch/maker_worker.py --connect``) against
    a bank in another process.

    ``register(kind)`` instantiates any of the four maker types as a
    ``MakerJob`` with its own batch size, pacing (``min_period_s``), and
    node slice; ``start()``/``stop()`` manage the fleet. The runtime owns
    the ``SharedFeatureStore`` the label/graph makers write to, and the
    trainer publishes its step counter on ``trainer_step`` so every job's
    ``ckpt_version_lag`` is measured against the live trainer clock.

    Maker types and what they touch:

    - ``embedding_refresh``: re-encode node tokens with the latest
      checkpoint, ``server.update`` the bank (needs ``ckpts`` +
      ``embed_fn``).
    - ``label_mining``: embed a node batch, classify it against
      per-class centroids of currently-labeled bank rows (read back via
      ``server.lookup`` — the maker is a bank CLIENT, not an owner), and
      gate-write labels to the feature store.
    - ``graph_agreement``: embed a node batch with the latest checkpoint,
      fetch its nearest bank neighbors via ``server.nn_search``, and
      gate-write the labeled-neighbor weighted vote.
    - ``graph_builder``: read rows via ``server.lookup``, find top-k
      neighbors via ``server.nn_search``, write the dynamic graph. Needs
      no checkpoint — it runs even in trainer-less serving.
    """

    MAKER_KINDS = ("embedding_refresh", "label_mining", "graph_agreement",
                   "graph_builder")

    def __init__(self, server: KBClient,
                 corpus: Optional[SyntheticGraphCorpus] = None, *,
                 num_entries: Optional[int] = None,
                 ckpts: Optional[MemoryCheckpointStore] = None,
                 embed_fn: Optional[Callable] = None,
                 feature_store: Optional[SharedFeatureStore] = None,
                 num_classes: Optional[int] = None,
                 conf_threshold: float = 0.6, label_temp: float = 20.0,
                 agreement_k: int = 8, agreement_overfetch: int = 4,
                 builder_k: int = 8, centroid_sample: int = 256,
                 seed_labels: bool = True, seed_conf: float = 0.5):
        self.server, self.corpus = server, corpus
        self.ckpts, self.embed_fn = ckpts, embed_fn
        if corpus is None and num_entries is None:
            # the client duck-type carries the bank geometry (handshake or
            # live engine), so corpus-less runtimes need no explicit size
            num_entries = getattr(server, "num_entries", None)
        if corpus is None and num_entries is None:
            raise ValueError("MakerRuntime needs a corpus or num_entries "
                             "(trainer-less serving runs only the "
                             "checkpoint-free makers)")
        self.num_nodes = (corpus.num_nodes if corpus is not None
                          else num_entries)
        self.num_classes = (num_classes if num_classes is not None
                            else corpus.num_clusters if corpus is not None
                            else 1)
        self.conf_threshold = conf_threshold
        self.label_temp = label_temp
        self.agreement_k = agreement_k
        self.agreement_overfetch = agreement_overfetch
        self.builder_k = builder_k
        self.centroid_sample = centroid_sample
        self.feature_store = feature_store or SharedFeatureStore(
            self.num_nodes,
            max(builder_k, corpus.neighbors_per_node
                if corpus is not None else builder_k))
        if seed_labels and feature_store is None and corpus is not None:
            # the semi-supervised ground state (§4.2): the corpus's (noisy)
            # labeled subset enters at a low seed confidence, so makers can
            # out-vote it but never start from an unlabelable vacuum
            lab = np.asarray(corpus.labeled_ids)
            if lab.size:
                self.feature_store.update_labels(
                    lab, corpus.noisy_labels[lab].astype(np.int32),
                    np.full(lab.size, seed_conf, np.float32))
        self.trainer_step = 0           # published by the trainer loop
        # label_mining's per-class centroids, cached across maker steps and
        # recomputed only when the loaded checkpoint changes (see
        # _label_mining_step); the hit counter is the observability hook
        self._centroid_cache: Optional[tuple] = None
        self.centroid_cache_hits = 0
        self.jobs: List[MakerJob] = []
        server.attach_maker_runtime(self)

    # -- the four maker step functions (params, ckpt_step, ids) -> rows ----

    def _node_tokens(self, ids: np.ndarray) -> jnp.ndarray:
        if self.corpus is None:
            raise ValueError("this maker kind needs a corpus")
        return jnp.asarray(self.corpus.node_tokens(ids)[:, :-1])

    def _embed(self, params, ids: np.ndarray) -> np.ndarray:
        if self.embed_fn is None:
            raise ValueError("this maker kind needs embed_fn (and ckpts)")
        return np.asarray(self.embed_fn(params, self._node_tokens(ids)))

    def _embedding_refresh_step(self, params, step: int, ids) -> int:
        self.server.update(ids, self._embed(params, ids), src_step=step)
        return ids.size

    def _label_mining_step(self, params, step: int, ids) -> int:
        """§4.2.1 online label mining, asynchronous form: the class
        read-out is the labeled-centroid classifier over CURRENT bank rows
        (fetched through the server like any other client).

        The centroids are CACHED between maker steps and recomputed only
        when the loaded checkpoint step changes: the labeled-row read-back
        is a full ``centroid_sample``-row server lookup, and paying it once
        per published checkpoint instead of once per maker step is what
        keeps a fast-pacing mining fleet from dominating bank traffic
        (``centroid_cache_hits`` counts the lookups saved). Within one
        checkpoint the classifier is intentionally frozen — bank rows
        written since the cache was built shift the centroids only after
        the next checkpoint publish, which is the same staleness contract
        every maker already runs under."""
        fs = self.feature_store
        cached = self._centroid_cache
        if cached is not None and cached[0] == step:
            cent = cached[1]
            self.centroid_cache_hits += 1
        else:
            lab = fs.labeled_ids(cap=self.centroid_sample)
            if lab.size == 0:
                return None             # idle: nothing to calibrate against
            lab_emb = self.server.lookup(lab,
                                         trainer_step=self.trainer_step)
            lab_cls = fs.labels()[lab]
            cent = np.zeros((self.num_classes, lab_emb.shape[1]),
                            np.float32)
            for c in range(self.num_classes):
                m = lab_cls == c
                if m.any():
                    cent[c] = lab_emb[m].mean(0)
            self._centroid_cache = (step, cent)
        emb = self._embed(params, ids)
        probs = np.asarray(jax.nn.softmax(
            jnp.asarray(emb @ cent.T * self.label_temp), -1))
        conf = probs.max(-1)
        pred = probs.argmax(-1).astype(np.int32)
        conf = np.where(conf >= self.conf_threshold, conf, 0.0)
        return fs.update_labels(ids, pred, conf)

    def _graph_agreement_step(self, params, step: int, ids) -> int:
        """§4.2.2, asynchronous form: candidates come from the server's
        nn_search over the live bank (over-fetched so enough LABELED ones
        survive the mask), the vote from the shared feature store."""
        labels = self.feature_store.labels()    # ONE snapshot per step
        if not (labels >= 0).any():
            return None                 # idle: an unlabeled bank can't vote
        emb = self._embed(params, ids)
        kfetch = self.agreement_k * self.agreement_overfetch
        scores, nids = self.server.nn_search(emb, k=kfetch)
        nbr_labels = labels[np.maximum(nids, 0)]
        ok = ((nids >= 0) & (nbr_labels >= 0)
              & (nids != np.asarray(ids)[:, None]))
        # electorate = the agreement_k NEAREST labeled survivors (results
        # are score-sorted), matching the sync path's k-sized vote; the
        # over-fetch only buys labeled candidates, never a wider vote
        ok &= np.cumsum(ok, axis=1) <= self.agreement_k
        pred, conf = vote_agreement_labels(
            scores, nids, np.where(ok, nbr_labels, -1),
            num_classes=self.num_classes)
        return self.feature_store.update_labels(ids, np.asarray(pred),
                                                np.asarray(conf))

    def _graph_builder_step(self, params, step: int, ids) -> int:
        """Dynamic graph discovery over the live bank; checkpoint-free, so
        it also serves as the maker a trainer-less serving deployment runs.
        Self-exclusion rides the server's exclude_ids path — the same
        engine feature the in-graph ``make_graph_builder`` uses."""
        q = self.server.lookup(ids, trainer_step=self.trainer_step)
        scores, nids = self.server.nn_search(
            q, k=self.builder_k, exclude_ids=np.asarray(ids)[:, None])
        return self.feature_store.update_neighbors(
            ids, nids, np.maximum(scores, 0.0))

    # -- registry / lifecycle ----------------------------------------------

    def register(self, kind: str, *, batch_size: int = 64,
                 min_period_s: float = 0.0,
                 node_slice: Optional[np.ndarray] = None,
                 name: Optional[str] = None) -> MakerJob:
        """Instantiate one maker job (not started). ``node_slice`` splits
        a node range across several jobs of the same kind; ``min_period_s``
        paces this job independently of every other."""
        if kind not in self.MAKER_KINDS:
            raise ValueError(f"unknown maker kind {kind!r} "
                             f"(want one of {self.MAKER_KINDS})")
        step_fn = getattr(self, f"_{kind}_step")
        needs_ckpt = kind != "graph_builder"
        if needs_ckpt and (self.ckpts is None or self.embed_fn is None):
            raise ValueError(f"maker {kind!r} needs ckpts and embed_fn")
        nodes = (np.arange(self.num_nodes) if node_slice is None
                 else np.asarray(node_slice))
        if nodes.size == 0:             # reject at setup: an empty slice
            raise ValueError(           # has no well-defined round-robin
                f"maker {kind!r} got an empty node slice (more jobs than "
                "nodes?)")
        job = MakerJob(self, name or f"{kind}{len(self.jobs)}", kind,
                       step_fn, nodes, batch_size=batch_size,
                       min_period_s=min_period_s, needs_ckpt=needs_ckpt)
        self.jobs.append(job)
        return job

    def start(self) -> "MakerRuntime":
        for j in self.jobs:
            if not j.is_alive():
                j.start()
        return self

    def stop(self, timeout_s: float = 30.0) -> None:
        for j in self.jobs:
            j.stop_event.set()
        for j in self.jobs:
            j.join(timeout=timeout_s)

    def stats(self) -> Dict[str, Dict]:
        """Per-maker counters, keyed by job name: ``maker_steps`` (batches
        computed — crashed batches count under ``errors`` instead), and
        ``rows_written`` (gate-accepted writes), and the
        checkpoint-staleness trio — ``ckpt_version_lag`` (cumulative
        trainer-steps of lag across the run), ``ckpt_version_lag_last``,
        and ``last_ckpt_step``."""
        out = {}
        for j in self.jobs:
            out[j.name] = {
                "kind": j.kind,
                "maker_steps": j.steps,
                "rows_written": j.rows_written,
                "ckpt_version_lag": j.lag_sum,
                "ckpt_version_lag_last": j.last_lag,
                "last_ckpt_step": (j.ckpt_steps_used[-1]
                                   if j.ckpt_steps_used else -1),
                "errors": j.errors,
                "error": repr(j.last_error) if j.last_error else None,
            }
        return out


def format_maker_stats(stats: Dict[str, Dict]) -> List[str]:
    """One printable line per maker — the single formatter every entry
    point shares, so a crashing maker is loudly visible everywhere its
    counters are shown."""
    lines = []
    for name, s in stats.items():
        line = (f"maker {name}: steps={s['maker_steps']} "
                f"rows_written={s['rows_written']} "
                f"ckpt_version_lag={s['ckpt_version_lag']} "
                f"(last={s['ckpt_version_lag_last']}, "
                f"ckpt={s['last_ckpt_step']})")
        if s.get("errors"):
            line += f" ERRORS={s['errors']} last={s['error']}"
        lines.append(line)
    return lines


@dataclass
class AsyncRunResult:
    losses: List[float]
    reg_losses: List[float]
    step_times: List[float]
    maker_refreshes: int
    mean_staleness: float
    final_params: dict = field(repr=False, default=None)
    server: KnowledgeBankServer = field(repr=False, default=None)
    maker_stats: Dict[str, Dict] = field(default_factory=dict)
    runtime: "MakerRuntime" = field(repr=False, default=None)


def run_async_training(model: LM, corpus: SyntheticGraphCorpus, *,
                       steps: int = 50, batch_size: int = 16,
                       num_makers: int = 1, maker_batch: int = 64,
                       ckpt_period: int = 5, lr: float = 1e-3,
                       reg_weight: Optional[float] = None,
                       lazy_update: bool = True,
                       use_makers: bool = True,
                       makers: Optional[Sequence[str]] = None,
                       maker_period_s: float = 0.0,
                       trainer_push: bool = False,
                       kb_backend: str = "dense",
                       coalesce: bool = True,
                       kb_client: Optional[KBClient] = None,
                       seed: int = 0) -> AsyncRunResult:
    """End-to-end asynchronous CARLS training on one host: the trainer loop
    plus a ``MakerRuntime`` fleet, all clients of one coalescing server.

    ``makers`` selects maker kinds by name (each registered once, paced by
    ``maker_period_s``); the default — ``num_makers`` embedding-refresh
    jobs over disjoint node slices — preserves the historical behaviour.
    ``trainer_push=True`` additionally pushes the trainer's own pooled
    sample embeddings to the bank each step ("synchronous maker" mode, the
    in-graph step's ``trainer_push`` as a server client).

    ``kb_client``: an already-connected bank client — typically a
    ``RemoteKnowledgeBank`` (``launch/train.py --kb-connect``) — used
    INSTEAD of constructing an in-process server; every trainer and maker
    KB call then goes over that client's transport, and the final close()
    drops only this process's connection, never the remote bank."""
    from repro.optim import constant_lr
    cfg = model.cfg
    dist = DistContext()
    opt = AdamW(lr=constant_lr(lr), weight_decay=0.0)
    params = model.init(jax.random.key(seed))
    opt_state = opt.init(params)
    train_core, embed_fn = make_async_train_fns(model, opt, dist,
                                                reg_weight=reg_weight)
    if kb_client is not None:
        if kb_client.num_entries < corpus.num_nodes:
            raise ValueError(
                f"remote bank holds {kb_client.num_entries} entries but the "
                f"corpus has {corpus.num_nodes} nodes")
        if kb_client.dim != cfg.d_model:
            raise ValueError(f"remote bank dim {kb_client.dim} != model "
                             f"d_model {cfg.d_model}")
        server = kb_client
    else:
        # a sharded bank spans every local device (the trainer's mesh stays
        # as-is)
        server = KnowledgeBankServer(
            corpus.num_nodes, cfg.d_model, backend=kb_backend,
            lazy_lr=cfg.carls.lazy_lr, zmax=cfg.carls.outlier_zmax,
            lazy_update=lazy_update, coalesce=coalesce)
    ckpts = MemoryCheckpointStore()
    ckpts.save(0, params)
    runtime = None
    if use_makers:
        runtime = MakerRuntime(server, corpus, ckpts=ckpts,
                               embed_fn=embed_fn)
        if makers is None:
            for i, s in enumerate(np.array_split(
                    np.arange(corpus.num_nodes), num_makers)):
                runtime.register("embedding_refresh", batch_size=maker_batch,
                                 node_slice=s, name=f"maker{i}",
                                 min_period_s=maker_period_s)
        else:
            for kind in makers:
                runtime.register(kind, batch_size=maker_batch,
                                 min_period_s=maker_period_s)
        runtime.start()

    rng = np.random.default_rng(seed + 1)
    losses, regs, times = [], [], []
    try:
        for step in range(steps):
            if runtime is not None:
                runtime.trainer_step = step
            batch = corpus.batch(rng, batch_size)
            nbr_emb = server.lookup(batch["neighbor_ids"], trainer_step=step)
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            t0 = time.perf_counter()
            params, opt_state, pooled, gn, metrics = train_core(
                params, opt_state, jb, jnp.asarray(nbr_emb))
            jax.block_until_ready(pooled)
            times.append(time.perf_counter() - t0)
            server.lazy_grad(batch["neighbor_ids"], np.asarray(gn))
            if trainer_push:
                server.update(batch["sample_ids"], np.asarray(pooled),
                              src_step=step)
            losses.append(float(metrics["loss"]))
            regs.append(float(metrics.get("graph_reg", 0.0)))
            if (step + 1) % ckpt_period == 0:
                ckpts.save(step + 1, params)
    finally:        # a failed step must not leak maker/dispatcher threads
        if runtime is not None:
            runtime.stop(timeout_s=5.0)
        server.close()
    return AsyncRunResult(
        losses=losses, reg_losses=regs, step_times=times,
        maker_refreshes=(sum(j.steps for j in runtime.jobs)
                         if runtime else 0),
        mean_staleness=server.mean_staleness,
        final_params=params, server=server,
        maker_stats=runtime.stats() if runtime else {},
        runtime=runtime)
