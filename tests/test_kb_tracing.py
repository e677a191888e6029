"""The Knowledge Bank server's own measurement: host-time counters in
``KnowledgeBankServer.metrics`` / ``KBEngine``, ``kb.*`` profiler spans
around the dispatcher's and the engine's work, and stable names for the
engine's jitted programs and kernels.

CPU only. The spans are read back from a profiler trace recorded on the
CPU, which holds host spans but no device plane.
"""
import glob
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KBEngine, KnowledgeBankServer

N, D, K = 256, 16, 4
CLIENTS, CALLS = 4, 12


def _traffic(server, seed: int, results: list, lock: threading.Lock):
    """One client: lookups, lazy_grads and searches, in a fixed order."""
    rng = np.random.default_rng(seed)
    for j in range(CALLS):
        ids = rng.integers(0, N, 8)
        if j % 3 == 0:
            out = server.lookup(ids)
        elif j % 3 == 1:
            out = server.lazy_grad(ids, rng.standard_normal((8, D)))
        else:
            out = server.nn_search(rng.standard_normal((2, D)), K)
        with lock:
            results.append((seed, j, out))


def _run_clients(server):
    results, lock = [], threading.Lock()
    threads = [threading.Thread(target=_traffic,
                                args=(server, c, results, lock))
               for c in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return results


def _filled_server(backend="dense", **kw):
    srv = KnowledgeBankServer(N, D, backend=backend, **kw)
    srv.update(np.arange(N),
               np.random.default_rng(0).standard_normal((N, D)))
    return srv


@pytest.mark.parametrize("coalesce", [True, False])
def test_counters_add_up_under_threaded_traffic(coalesce):
    srv = _filled_server(coalesce=coalesce)
    _run_clients(srv)
    srv.close()                 # the last batch is accounted
    m = srv.stats()["metrics"]
    assert 0 < m["engine_wait_s"] <= m["engine_op_s"]
    assert m["engine_op_s"] <= m["engine_call_s"]
    assert m["engine_call_s"] <= m["dispatcher_busy_s"]
    if coalesce:
        assert m["queue_wait_s"] > 0
    else:                       # no queue: each call runs on its caller
        assert m["queue_wait_s"] == 0


# -- spans -----------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Spans of a coalescing server's dispatcher thread under threaded
    traffic, plus one direct engine call, from a CPU profiler trace:
    [(name, start_ns, end_ns, stats)] per host thread."""
    from jax.profiler import ProfileData
    srv = _filled_server()
    srv.lookup(np.arange(8))            # compile outside the trace
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        _run_clients(srv)
        srv.engine.lookup(np.arange(4))
    finally:
        jax.profiler.stop_trace()
        srv.close()
    path = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    threads = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      {k: v for k, v in e.stats}) for e in line.events
                     if e.name.startswith("kb.")]
            if spans:
                threads.append(spans)
    return threads


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("op,wait", [("lookup", True),
                                     ("lazy_grad", False),
                                     ("nn_search", True)])
def test_spans_nest_run_engine_wait(recorded, op, wait):
    dispatcher = [t for t in recorded
                  if any(s[0] == "kb.dispatch.wait" for s in t)]
    assert len(dispatcher) == 1
    spans = dispatcher[0]
    runs = [s for s in spans if s[0] == "kb.run"
            and s[3]["op"] == {"nn_search": "nn"}.get(op, op)]
    engine = [s for s in spans if s[0] == f"kb.engine.{op}"]
    assert runs and len(engine) == len(runs)
    for run in runs:
        assert run[3]["n_req"] >= 1 and run[3]["n_ids"] >= 0
        inner = [s for s in engine if _inside(s, run)]
        assert len(inner) == 1 and inner[0][3]["run"] == run[3]["run"]
        for child in ("kb.run.args", "kb.run.reply"):
            assert sum(s[0] == child and _inside(s, run)
                       for s in spans) == 1
        waits = [s for s in spans if s[0] == "kb.engine.wait"
                 and _inside(s, inner[0])]
        assert len(waits) == (1 if wait else 0)
    # run numbers are a sequence over every op the dispatcher ran
    seq = sorted(s[3]["run"] for s in spans if s[0] == "kb.run")
    assert seq == list(range(seq[0], seq[0] + len(seq)))


def test_direct_engine_call_is_tagged_minus_one(recorded):
    direct = [s for t in recorded for s in t
              if s[0] == "kb.engine.lookup" and s[3]["run"] == -1]
    assert len(direct) == 1


# -- stable names ----------------------------------------------------------


def _args(eng, name):
    st, b = eng.state, 8
    ids = jnp.zeros((b,), jnp.int32)
    g, m = jnp.zeros((b, D), jnp.float32), jnp.ones((b,), jnp.float32)
    q = jnp.zeros((b, D), jnp.float32)
    qsc = (eng._qscale, eng._qoffset) if eng._quantized else ()
    return {"_lookup_fn": (st, *qsc, ids), "_update_fn": (st, *qsc, ids, g),
            "_flush_fn": (st, *qsc), "_lazy_fn": (st, ids, g, m),
            "_immediate_fn": (st, ids, g, m)}.get(name, (st, *qsc, q))


@pytest.mark.parametrize("storage,attr,jit_name,kernel", [
    ("fp32", "_lookup_fn", "kb_lookup", None),
    ("int8", "_lookup_fn", "kb_lookup_q", "kb_fused_lookup_q"),
    ("fp32", "_update_fn", "kb_update", None),
    ("fp32", "_lazy_fn", "kb_lazy_grad", None),
    ("fp32", "_immediate_fn", "kb_immediate_grad", None),
    ("fp32", "_flush_fn", "kb_flush", None),
    ("fp32", "nn_exact", "kb_nn_exact", None),
    ("fp32", "nn_ivf", "kb_nn_ivf", "ivf_stage2"),
    ("int8", "nn_ivf", "kb_nn_ivf", "ivf_stage2_q"),
])
def test_jitted_engine_functions_have_stable_names(storage, attr, jit_name,
                                                   kernel):
    eng = KBEngine(N, D, backend="pallas", storage=storage,
                   search_mode="ivf", ann_nlist=4, ann_nprobe=2)
    if attr == "nn_exact":
        eng.nn_search(np.zeros((2, D), np.float32), K, mode="exact")
        fn, args = eng._nn_fns[K], _args(eng, attr)
    elif attr == "nn_ivf":
        eng.update(np.arange(N),
                   np.random.default_rng(0).standard_normal((N, D)))
        eng.rebuild_ann_index(iters=2)
        eng.nn_search(np.zeros((2, D), np.float32), K)
        (fn,) = eng._ivf_fns.values()
        idx = eng.ann_index
        tail = ((idx.centroids, idx.packed_codes, idx.packed_scale,
                 idx.packed_offset, idx.packed_ids) if eng._quantized
                else (idx.centroids, idx.packed_vecs, idx.packed_ids))
        qsc = (eng._qscale, eng._qoffset) if eng._quantized else ()
        args = (eng.state.table, *qsc, *tail, idx.bucket_occ,
                jnp.zeros((8, D), jnp.float32))
    else:
        fn, args = getattr(eng, attr), _args(eng, attr)
    assert fn.lower(*args).as_text().startswith(f"module @jit_{jit_name} ")
    if kernel is not None:
        jaxpr = str(jax.make_jaxpr(fn)(*args))
        assert re.search(rf"\bname={kernel}\n", jaxpr)


# -- the profiler changes nothing ------------------------------------------


@pytest.mark.parametrize("backend", ["dense", "pallas", "sharded"])
def test_results_identical_with_and_without_profiler(backend, tmp_path):
    def serial(profiled: bool):
        srv = _filled_server(backend, coalesce=False)
        args_before = srv.stats()["metrics"]["engine_args_s"]
        if profiled:
            jax.profiler.start_trace(str(tmp_path))
        try:
            results, lock = [], threading.Lock()
            for c in range(2):
                _traffic(srv, c, results, lock)
            srv.flush()
            table = srv.table_snapshot()
        finally:
            if profiled:
                jax.profiler.stop_trace()
            srv.close()
        assert srv.stats()["metrics"]["engine_args_s"] > args_before
        return results, table

    (plain, t_plain), (traced, t_traced) = serial(False), serial(True)
    np.testing.assert_array_equal(t_plain, t_traced)
    # the engine's argument placement is a span of its own inside each
    # lookup and lazy_grad, tagged with that op's run and the shard count
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("kb.engine.")]
    args = [s for s in spans if s[0] == "kb.engine.args"]
    ops = [s for s in spans
           if s[0] in ("kb.engine.lookup", "kb.engine.lazy_grad")]
    assert len(args) == len(ops) == 2 * CALLS * 2 // 3
    for a in args:
        (op,) = [o for o in ops if _inside(a, o)]
        assert a[3] == {"run": op[3]["run"], "shards": 1}
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        assert a[:2] == b[:2]
        for x, y in zip(jax.tree.leaves(a[2]), jax.tree.leaves(b[2])):
            np.testing.assert_array_equal(x, y)
