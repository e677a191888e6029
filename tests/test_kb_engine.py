"""KB engine: backend parity (dense == sharded == pallas, bit-for-bit on
the same op sequence), state donation (every op consumes the state it is
given; warm-up leaves it alone), coalescing-server correctness under
concurrency, and the IVF search mode — recall, exact fallback, coalesced
determinism, background refresh under donating writes."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DenseBackend, KBEngine, KnowledgeBankServer,
                        PallasBackend, ShardedBackend, kb_create,
                        kb_lazy_grad, kb_lookup, make_backend)
from repro.core import knowledge_bank as kbm
from repro.launch.mesh import make_host_mesh
from repro.sharding.partition import DistContext

N, D = 64, 16
LAZY_LR, ZMAX = 0.2, 2.0


def _backends():
    mesh = make_host_mesh((1, 1), ("data", "model"))
    return {
        "dense": DenseBackend(),
        "sharded": ShardedBackend(DistContext(mesh=mesh)),
        "pallas": PallasBackend(),
    }


def _state_allclose(a, b, label):
    np.testing.assert_allclose(np.asarray(a.table), np.asarray(b.table),
                               atol=1e-6, err_msg=f"{label}: table")
    np.testing.assert_array_equal(np.asarray(a.version),
                                  np.asarray(b.version),
                                  err_msg=f"{label}: version")
    np.testing.assert_allclose(np.asarray(a.grad_sum),
                               np.asarray(b.grad_sum), atol=1e-6,
                               err_msg=f"{label}: grad_sum")
    np.testing.assert_array_equal(np.asarray(a.grad_cnt),
                                  np.asarray(b.grad_cnt),
                                  err_msg=f"{label}: grad_cnt")
    np.testing.assert_allclose(np.asarray(a.grad_sqnorm),
                               np.asarray(b.grad_sqnorm), atol=1e-6,
                               err_msg=f"{label}: grad_sqnorm")
    np.testing.assert_allclose(np.asarray(a.norm_ema),
                               np.asarray(b.norm_ema), atol=1e-6,
                               err_msg=f"{label}: norm_ema")


def test_backend_parity_full_op_sequence():
    """The same op sequence — lazy_grad (dup ids), lookup (dup ids), update,
    lazy_grad, flush, nn_search — leaves every backend in the same state and
    returns the same values."""
    backends = _backends()
    states = {k: kb_create(N, D, key=jax.random.key(0)) for k in backends}
    ids = jnp.array([3, 17, 42, 3, 63])                 # note the dup
    grads = jax.random.normal(jax.random.key(1), (5, D))
    vals_upd = jax.random.normal(jax.random.key(2), (5, D))
    q = jax.random.normal(jax.random.key(3), (4, D))

    outs = {}
    for name, bk in backends.items():
        st = states[name]
        st = bk.lazy_grad(st, ids, grads, zmax=ZMAX)
        v1, st = bk.lookup(st, ids, lazy_lr=LAZY_LR, zmax=ZMAX)
        st = bk.update(st, ids, vals_upd)
        st = bk.lazy_grad(st, ids, 0.5 * grads, zmax=ZMAX)
        st = bk.flush(st, lazy_lr=LAZY_LR, zmax=ZMAX)
        s, i = bk.nn_search(st, q, 5)
        states[name] = st
        outs[name] = (np.asarray(v1), np.asarray(s), np.asarray(i))

    for name in ("sharded", "pallas"):
        _state_allclose(states["dense"], states[name], f"dense vs {name}")
        np.testing.assert_allclose(outs["dense"][0], outs[name][0],
                                   atol=1e-5, err_msg=f"{name}: lookup vals")
        np.testing.assert_allclose(outs["dense"][1], outs[name][1],
                                   atol=1e-5, err_msg=f"{name}: nn scores")
        np.testing.assert_array_equal(outs["dense"][2], outs[name][2],
                                      err_msg=f"{name}: nn ids")


def test_pallas_fused_lookup_is_one_call_semantics():
    """Fused kernel path == dense kb_lookup including cache clears and the
    once-per-touched-row version bump under duplicate ids."""
    bk = PallasBackend()
    kb_d = kb_create(N, D, key=jax.random.key(5))
    kb_p = kb_create(N, D, key=jax.random.key(5))
    ids = jnp.array([7, 7, 7, 9])
    g = jax.random.normal(jax.random.key(6), (4, D))
    kb_d = kb_lazy_grad(kb_d, ids, g)
    kb_p = bk.lazy_grad(kb_p, ids, g, zmax=0.0)
    v_d, kb_d = kb_lookup(kb_d, ids, lazy_lr=LAZY_LR, zmax=ZMAX)
    v_p, kb_p = bk.lookup(kb_p, ids, lazy_lr=LAZY_LR, zmax=ZMAX)
    np.testing.assert_allclose(np.asarray(v_d), np.asarray(v_p), atol=1e-6)
    _state_allclose(kb_d, kb_p, "fused lookup")
    assert int(kb_p.version[7]) == 1        # once, not thrice
    assert float(kb_p.grad_cnt.sum()) == 0.0


# Each case: the looked-up ids, the rows holding pending gradients (one
# unit-scale batch each, then the case's extra batch), and the lookup's
# zmax. The apply-side clip engages only for zmax < 1: the average of a
# row's cached gradients is never longer than their rms.
_LOOKUP_CASES = {
    "repeated_ids": ([7, 7, 9, 7, 9, 30], [7, 9], ZMAX),
    "pow2_padded": ([3, 11, 12, 5, 40, 40, 40, 40], [3, 5, 40], ZMAX),
    "pending_and_clean": ([1, 2, 3, 4, 5, 6], [2, 4, 6], ZMAX),
    "outlier_clip": ([21, 22, 23], [21, 22], 0.5),
    "first_and_last_row": ([0, N - 1, 0], [0, N - 1], ZMAX),
}


@pytest.mark.parametrize("case", list(_LOOKUP_CASES))
def test_pallas_lookup_gathers_only_requested_rows(case):
    """``PallasBackend.lookup`` == ``kb_lookup(apply_pending=True)`` bit for
    bit; the requested rows read the float64 clipped pending average, every
    occurrence of an id the same row; every row outside the ids — table,
    caches, ``norm_ema``, version — is bit-identical before and after."""
    ids_l, pending, zmax = _LOOKUP_CASES[case]
    ids = jnp.asarray(ids_l, jnp.int32)
    kb = kb_create(N, D, key=jax.random.key(11))
    rng = np.random.default_rng(12)
    pend = jnp.asarray(pending, jnp.int32)
    g = rng.normal(size=(len(pending), D)).astype(np.float32)
    kb = kb_lazy_grad(kb, pend, jnp.asarray(g), zmax=ZMAX)
    # a second batch: the first pending row gets a 10x outlier, which the
    # entry-side clip cuts against the norm EMA the first batch seeded
    g2 = rng.normal(size=(len(pending), D)).astype(np.float32)
    g2[0] *= 10.0
    kb = kb_lazy_grad(kb, pend, jnp.asarray(g2), zmax=ZMAX)
    before = jax.tree.map(np.asarray, kb)

    v_p, kb_p = PallasBackend().lookup(kb, ids, lazy_lr=LAZY_LR, zmax=zmax)
    v_d, kb_d = kb_lookup(kb, ids, lazy_lr=LAZY_LR, zmax=zmax)
    np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_d))
    for leaf_p, leaf_d in zip(kb_p, kb_d):
        np.testing.assert_array_equal(np.asarray(leaf_p), np.asarray(leaf_d))
    if case == "pow2_padded":
        # the engine pads with the last real id: the pads change nothing
        v_u, kb_u = kb_lookup(kb, ids[:5], lazy_lr=LAZY_LR, zmax=zmax)
        np.testing.assert_array_equal(np.asarray(v_p)[:5], np.asarray(v_u))
        for leaf_p, leaf_u in zip(kb_p, kb_u):
            np.testing.assert_array_equal(np.asarray(leaf_p),
                                          np.asarray(leaf_u))

    after = jax.tree.map(np.asarray, kb_p)
    touched = np.unique(ids_l)
    out = np.setdiff1d(np.arange(N), touched)
    for name in ("table", "grad_sum", "grad_cnt", "grad_sqnorm", "version"):
        np.testing.assert_array_equal(getattr(after, name)[out],
                                      getattr(before, name)[out],
                                      err_msg=name)
    np.testing.assert_array_equal(after.norm_ema, before.norm_ema)
    assert not after.grad_sum[touched].any()
    assert not after.grad_cnt[touched].any()
    assert not after.grad_sqnorm[touched].any()
    had = before.grad_cnt[touched] > 0
    np.testing.assert_array_equal(after.version[touched],
                                  before.version[touched] + had)

    # the pending apply in float64, from the state before the call
    cnt = np.maximum(before.grad_cnt, 1.0)[:, None].astype(np.float64)
    avg = before.grad_sum / cnt
    nrm = np.linalg.norm(avg, axis=-1, keepdims=True)
    cap = zmax * np.sqrt(before.grad_sqnorm[:, None] / cnt)
    scale = np.minimum(1.0, cap / np.maximum(nrm, 1e-12))
    want = np.where(before.grad_cnt[:, None] > 0,
                    before.table - LAZY_LR * avg * scale, before.table)
    vals = np.asarray(v_p)
    np.testing.assert_allclose(vals, want[ids_l], rtol=0, atol=1e-6)
    np.testing.assert_array_equal(after.table[touched], vals[
        [ids_l.index(i) for i in touched]])
    for i in touched:
        first = vals[ids_l.index(i)]
        for j in np.flatnonzero(np.asarray(ids_l) == i):
            np.testing.assert_array_equal(vals[j], first)
    if case == "outlier_clip":
        assert scale[ids_l[0], 0] < 1.0      # the apply-side clip ran


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_engine_bucket_padding_is_invisible(backend):
    """Engine results at awkward batch sizes (pow2-padded internally) match
    the unpadded functional ops."""
    eng = KBEngine(N, D, backend=backend, lazy_lr=LAZY_LR, zmax=ZMAX,
                   key=jax.random.key(0))
    ref = kb_create(N, D, key=jax.random.key(0))
    rng = np.random.default_rng(0)
    for size in (1, 3, 5, 9, 17):
        ids = rng.integers(0, N, (size,)).astype(np.int32)
        g = rng.normal(size=(size, D)).astype(np.float32)
        eng.lazy_grad(ids, g)
        ref = kb_lazy_grad(ref, jnp.asarray(ids), jnp.asarray(g), zmax=ZMAX)
        vals = eng.lookup(ids)
        ref_vals, ref = kb_lookup(ref, jnp.asarray(ids), lazy_lr=LAZY_LR,
                                  zmax=ZMAX)
        np.testing.assert_allclose(vals, np.asarray(ref_vals), atol=1e-5)
    np.testing.assert_allclose(eng.table_snapshot(), np.asarray(ref.table),
                               atol=1e-5)
    np.testing.assert_array_equal(eng.version_snapshot(),
                                  np.asarray(ref.version))


def _live(eng):
    """Every array of the engine's bank state (int8 side-cars included)."""
    return jax.tree.leaves((eng.state, eng._qscale, eng._qoffset))


def _reference_ops(storage, lazy):
    """The plain functional ops, jitted without donation. Each takes and
    returns the state as the engine holds it: a tuple of the ``KBState``
    and, for int8, its scale and offset."""
    def lookup(st, ids):
        if storage == "int8":
            vals, *st = kbm.kb_lookup_q(*st, ids, lazy_lr=LAZY_LR, zmax=ZMAX)
            return vals, tuple(st)
        vals, kb = kb_lookup(st[0], ids, lazy_lr=LAZY_LR, zmax=ZMAX,
                             apply_pending=lazy)
        return vals, (kb,)

    def update(st, ids, values):
        if storage == "int8":
            return kbm.kb_update_q(*st, ids, values)
        return (kbm.kb_update(st[0], ids, values),)

    def grad(st, ids, g):
        kb = (kb_lazy_grad(st[0], ids, g, zmax=ZMAX) if lazy else
              st[0]._replace(table=st[0].table.at[ids].add(-LAZY_LR * g)))
        return (kb, *st[1:])

    return jax.jit(lookup), jax.jit(update), jax.jit(grad)


_DONATING = [("dense", "fp32", True), ("pallas", "fp32", True),
             ("sharded", "fp32", True), ("dense", "int8", True),
             ("pallas", "int8", True), ("dense", "fp32", False),
             ("pallas", "fp32", False), ("sharded", "fp32", False)]


@pytest.mark.parametrize(
    "backend,storage,lazy", _DONATING,
    ids=[f"{b}-{s}-{'lazy' if lz else 'immediate'}"
         for b, s, lz in _DONATING])
def test_engine_ops_donate_the_state(backend, storage, lazy):
    """Each lookup, update and lazy (or immediate) grad consumes the state
    it was given: every leaf of it is deleted afterwards, so XLA scattered
    in place, and ``donation_misses`` stays 0. Served rows and the final
    state are bit-identical to the plain functional ops run un-donated on
    the same batches (power-of-two sizes and distinct update ids, so the
    engine pads and dedupes nothing)."""
    eng = KBEngine(N, D, backend=_backends()[backend], storage=storage,
                   lazy_update=lazy, lazy_lr=LAZY_LR, zmax=ZMAX)
    ref = jax.tree.map(jnp.copy, (eng.state, eng._qscale, eng._qoffset))
    ref = ref if storage == "int8" else ref[:1]
    ref_lookup, ref_update, ref_grad = _reference_ops(storage, lazy)
    rng = np.random.default_rng(7)
    sequence = [("update", np.arange(N)), ("grad", rng.integers(0, N, 16)),
                ("lookup", rng.integers(0, N, 8)),
                ("grad", rng.integers(0, N, 32)),
                ("update", rng.permutation(N)[:16]),
                ("lookup", rng.integers(0, N, 32))]
    for op, ids in sequence:
        ids = ids.astype(np.int32)
        rows = rng.standard_normal((ids.size, D)).astype(np.float32)
        # a grad never touches the int8 scale and offset: it gets the
        # KBState alone
        before = jax.tree.leaves(eng.state) if op == "grad" else _live(eng)
        if op == "lookup":
            got = eng.lookup(ids)
            want, ref = ref_lookup(ref, jnp.asarray(ids))
            np.testing.assert_array_equal(got, np.asarray(want))
        elif op == "update":
            eng.update(ids, rows)
            ref = ref_update(ref, jnp.asarray(ids), jnp.asarray(rows))
        else:
            eng.lazy_grad(ids, rows)
            ref = ref_grad(ref, jnp.asarray(ids), jnp.asarray(rows))
        assert all(a.is_deleted() for a in before), op
        assert eng.donation_misses == 0, op
    for got, want in zip(_live(eng), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("backend,storage", [
    ("dense", "fp32"), ("pallas", "fp32"), ("sharded", "fp32"),
    ("pallas", "int8")])
def test_warmup_leaves_the_live_state_untouched(backend, storage):
    """``warmup`` runs its donating programs on a copy of the state: every
    live leaf survives bit-identical (pending gradients included, which a
    lookup on the live state would apply), and serving every warmed bucket
    afterwards compiles nothing new."""
    eng = KBEngine(N, D, backend=_backends()[backend], storage=storage,
                   lazy_lr=LAZY_LR, zmax=ZMAX, key=jax.random.key(3))
    rng = np.random.default_rng(3)
    ids = rng.integers(0, N, 8)
    eng.lazy_grad(ids, rng.standard_normal((8, D)).astype(np.float32))
    live = _live(eng)
    host = [np.array(jnp.copy(a)) for a in live]
    eng.warmup(32)
    assert not any(a.is_deleted() for a in live)
    for a, h in zip(_live(eng), host):
        np.testing.assert_array_equal(np.asarray(jnp.copy(a)), h)
    compiled = (eng._lookup_fn._cache_size(), eng._lazy_fn._cache_size())
    for b in (8, 16, 32):
        ids = rng.integers(0, N, b)
        eng.lookup(ids)
        eng.lazy_grad(ids, rng.standard_normal((b, D)).astype(np.float32))
    assert (eng._lookup_fn._cache_size(),
            eng._lazy_fn._cache_size()) == compiled
    assert eng.donation_misses == 0


def test_engine_update_dedupes_last_writer_wins():
    eng = KBEngine(N, D)
    ids = np.array([4, 4, 9])
    vals = np.stack([np.full(D, 1.0), np.full(D, 2.0), np.full(D, 3.0)])
    eng.update(ids, vals)
    tbl = eng.table_snapshot()
    np.testing.assert_allclose(tbl[4], 2.0)     # last write for id 4
    np.testing.assert_allclose(tbl[9], 3.0)
    assert eng.version_snapshot()[4] == 1       # one call -> one bump


def test_lazy_grad_duplicate_ids_keep_ema_bounded():
    """One call with m duplicates of a row advances the norm EMA by ONE
    decay step toward the mean contribution — never inflates it m-fold or
    drives it negative (the coalesced multi-client case)."""
    kb = kb_create(N, D)
    ids = jnp.zeros((12,), jnp.int32) + 5        # 12 duplicates of row 5
    g = jnp.ones((12, D))
    sq_one = float(jnp.sum(g[0] * g[0]))
    kb = kb_lazy_grad(kb, ids, g, zmax=2.0)
    ema = float(kb.norm_ema[5])
    assert ema == pytest.approx(sq_one)          # first call: mean sq, once
    kb = kb_lazy_grad(kb, ids, 0.1 * g, zmax=2.0)
    ema2 = float(kb.norm_ema[5])
    assert 0.0 < ema2 < ema                      # decays, stays positive


def test_engine_empty_batches_are_noops():
    eng = KBEngine(N, D, key=jax.random.key(0))
    before = eng.table_snapshot().copy()
    vals = eng.lookup(np.zeros((0,), np.int32))
    assert vals.shape == (0, D)
    eng.update(np.zeros((0,), np.int32), np.zeros((0, D)))
    eng.lazy_grad(np.zeros((0,), np.int32), np.zeros((0, D)))
    np.testing.assert_array_equal(eng.table_snapshot(), before)


def test_async_training_runs_on_sharded_backend():
    """kb_backend='sharded' builds its own host-meshed engine (regression:
    the documented third backend used to raise at server construction)."""
    from repro.configs import get_config
    from repro.core import run_async_training
    from repro.data import SyntheticGraphCorpus
    from repro.models import build_model
    cfg = get_config("yi-6b").reduced().replace(num_layers=2)
    model = build_model(cfg)
    corpus = SyntheticGraphCorpus(num_nodes=64, vocab_size=cfg.vocab_size,
                                  seq_len=17, neighbors_per_node=2)
    res = run_async_training(model, corpus, steps=3, batch_size=4,
                             use_makers=False, kb_backend="sharded")
    assert len(res.losses) == 3
    assert np.isfinite(res.losses).all()


def test_coalescing_server_merges_queued_lookups():
    """Requests enqueued while the dispatcher sleeps its coalescing window
    execute as (far) fewer device dispatches, with per-request results
    identical to serial execution."""
    srv = KnowledgeBankServer(N, D, coalesce=True, coalesce_window_s=0.05)
    serial = KBEngine(N, D)
    table = np.random.default_rng(0).normal(size=(N, D)).astype(np.float32)
    srv.update(np.arange(N), table)
    serial.update(np.arange(N), table)

    reqs, results = [], {}

    def do_lookup(t):
        results[t] = srv.lookup(np.arange(t, t + 8))

    threads = [threading.Thread(target=do_lookup, args=(t,))
               for t in range(16)]
    d0 = srv.metrics["dispatches"]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    merged_dispatches = srv.metrics["dispatches"] - d0
    srv.close()
    assert merged_dispatches < 16, merged_dispatches   # coalescing happened
    for t in range(16):
        np.testing.assert_allclose(results[t],
                                   serial.lookup(np.arange(t, t + 8)),
                                   atol=1e-6)


def test_coalescing_server_stress_matches_serial_baseline():
    """8 threads hammer lazy_grad + lookup concurrently; the final table and
    every served value must match a serial single-thread execution."""
    n_threads, rows_per = 8, 8
    grads = {t: np.random.default_rng(t).normal(
        size=(rows_per, D)).astype(np.float32) for t in range(n_threads)}
    ids_of = {t: np.arange(t * rows_per, (t + 1) * rows_per)
              for t in range(n_threads)}

    # serial baseline: same ops, one thread, plain engine
    serial = KBEngine(N, D, lazy_lr=LAZY_LR, zmax=ZMAX,
                      key=jax.random.key(9))
    for t in range(n_threads):
        serial.lazy_grad(ids_of[t], grads[t])
    serial_vals = serial.lookup(np.arange(N))

    srv = KnowledgeBankServer(N, D, lazy_lr=LAZY_LR, zmax=ZMAX,
                              engine=KBEngine(N, D, lazy_lr=LAZY_LR,
                                              zmax=ZMAX,
                                              key=jax.random.key(9)),
                              coalesce=True, coalesce_window_s=0.002)
    barrier = threading.Barrier(n_threads)
    served = {}

    def worker(t):
        barrier.wait()
        srv.lazy_grad(ids_of[t], grads[t])      # disjoint rows: commutative
        barrier.wait()
        # overlapping lookups: first application wins, everyone must see
        # the same post-apply rows regardless of merge order
        served[t] = srv.lookup(np.arange(N))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    srv.close()

    np.testing.assert_allclose(srv.engine.table_snapshot(),
                               serial.table_snapshot(), atol=1e-5)
    for t in range(n_threads):
        np.testing.assert_allclose(served[t], serial_vals, atol=1e-5,
                                   err_msg=f"thread {t} served values")
    assert srv.metrics["requests"] == 2 * n_threads
    assert srv.metrics["dispatches"] <= srv.metrics["requests"]


def test_server_close_then_call_fails_fast():
    """Post-close requests fail fast with a typed error (ISSUE 5: they
    used to fall through to a direct path — and could hang forever when
    racing the drain); read-only snapshots of the drained server stay
    legal for result summaries."""
    from repro.core import KBServerClosedError
    srv = KnowledgeBankServer(N, D)
    srv.update(np.array([1]), np.ones((1, D)))
    srv.close()
    with pytest.raises(KBServerClosedError):
        srv.lookup(np.array([1]))
    np.testing.assert_allclose(srv.table_snapshot()[1], 1.0)


def test_make_backend_rejects_unknown():
    with pytest.raises(ValueError):
        make_backend("bigtable")


# ---------------------------------------------------------------------------
# ISSUE 2: sharded exclude_ids + IVF search mode
# ---------------------------------------------------------------------------

def _clustered_table(n, d, n_centers, seed=0):
    from repro.core.ann_index import clustered_bank
    return clustered_bank(n, d, n_centers, noise=0.1, seed=seed)


def test_sharded_nn_search_exclude_ids_matches_dense():
    """exclude_ids on the sharded backend (used to raise): over-fetch k+E
    candidates, mask excluded post-merge — bit-identical to the dense
    pre-mask semantics."""
    backends = _backends()
    table = np.random.default_rng(3).normal(size=(N, D)).astype(np.float32)
    q = jnp.asarray(table[:4] + 0.01)
    exclude = jnp.asarray([[0, 1, -1], [1, 2, 3], [-1, -1, -1], [3, 7, 9]])
    outs = {}
    for name, bk in backends.items():
        st = kb_create(N, D)
        st = bk.update(st, jnp.arange(N), jnp.asarray(table))
        outs[name] = bk.nn_search(st, q, 5, exclude_ids=exclude)
    for name in ("sharded", "pallas"):
        np.testing.assert_allclose(np.asarray(outs["dense"][0]),
                                   np.asarray(outs[name][0]), atol=1e-5,
                                   err_msg=f"{name}: excluded scores")
        np.testing.assert_array_equal(np.asarray(outs["dense"][1]),
                                      np.asarray(outs[name][1]),
                                      err_msg=f"{name}: excluded ids")
        for b in range(4):
            got = set(np.asarray(outs[name][1])[b].tolist())
            banned = {int(e) for e in np.asarray(exclude)[b] if e >= 0}
            assert not (got & banned), (name, b)


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_engine_ivf_recall_on_clustered_data(backend):
    n, d = 1024, 16
    table = _clustered_table(n, d, 16, seed=1)
    eng = KBEngine(n, d, backend=backend, search_mode="ivf",
                   ann_nlist=16, ann_nprobe=4)
    eng.update(np.arange(n), table)
    eng.rebuild_ann_index()
    q = table[np.arange(0, n, 64)] + 0.01
    _, exact_ids = eng.nn_search(q, 10, mode="exact")
    _, ivf_ids = eng.nn_search(q, 10)
    assert eng.search_stats == {"exact": 1, "ivf": 1}
    recall = np.mean([len(set(exact_ids[b]) & set(ivf_ids[b])) / 10
                      for b in range(q.shape[0])])
    assert recall >= 0.95, recall


def test_engine_ivf_falls_back_exact_when_absent_or_stale():
    n, d = 256, 8
    table = _clustered_table(n, d, 8, seed=2)
    ref_eng = KBEngine(n, d, search_mode="exact")
    eng = KBEngine(n, d, search_mode="ivf", ann_nlist=8, ann_nprobe=8,
                   ann_stale_rows=16)
    for e in (ref_eng, eng):
        e.update(np.arange(n), table)
    q = table[:4] + 0.01

    # no index yet -> exact fallback, identical results
    s0, i0 = eng.nn_search(q, 5)
    s_ref, i_ref = ref_eng.nn_search(q, 5)
    np.testing.assert_array_equal(i0, i_ref)
    np.testing.assert_allclose(s0, s_ref, atol=1e-6)
    assert eng.search_stats["exact"] == 1 and eng.search_stats["ivf"] == 0

    eng.rebuild_ann_index()
    eng.nn_search(q, 5)
    assert eng.search_stats["ivf"] == 1

    # write past the staleness budget -> exact fallback again
    rng = np.random.default_rng(0)
    eng.update(np.arange(32), rng.normal(size=(32, d)).astype(np.float32))
    assert eng.ann_staleness_rows > eng.ann_stale_rows
    eng.nn_search(q, 5)
    assert eng.search_stats == {"exact": 2, "ivf": 1}

    # a rebuild restores the IVF path
    eng.rebuild_ann_index()
    eng.nn_search(q, 5)
    assert eng.search_stats == {"exact": 2, "ivf": 2}


def test_coalesced_ivf_searches_are_deterministic():
    """IVF results are a pure function of (index, table, query): a search
    merged into one batched two-stage call returns exactly what the same
    search returns solo."""
    n, d = 512, 16
    table = _clustered_table(n, d, 8, seed=4)
    solo = KBEngine(n, d, search_mode="ivf", ann_nlist=8, ann_nprobe=2)
    solo.update(np.arange(n), table)
    solo.rebuild_ann_index()
    queries = {t: table[t * 8:t * 8 + 4] + 0.01 for t in range(8)}
    expected = {t: solo.nn_search(queries[t], 5) for t in range(8)}

    eng = KBEngine(n, d, search_mode="ivf", ann_nlist=8, ann_nprobe=2)
    eng.update(np.arange(n), table)
    eng.rebuild_ann_index()
    srv = KnowledgeBankServer(engine=eng, coalesce=True,
                              coalesce_window_s=0.05)
    results = {}

    def do_search(t):
        results[t] = srv.nn_search(queries[t], 5)

    threads = [threading.Thread(target=do_search, args=(t,))
               for t in range(8)]
    d0 = srv.metrics["dispatches"]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    merged = srv.metrics["dispatches"] - d0
    srv.close()
    assert merged < 8, merged                     # searches actually merged
    assert eng.search_stats["exact"] == 0         # served from the index
    for t in range(8):
        np.testing.assert_array_equal(results[t][1], expected[t][1],
                                      err_msg=f"thread {t} ids")
        np.testing.assert_allclose(results[t][0], expected[t][0], atol=1e-5,
                                   err_msg=f"thread {t} scores")


def test_nn_requests_with_different_modes_do_not_merge():
    from repro.core.async_runtime import _Request, _mergeable
    a = _Request("nn", payload=np.zeros((2, 4)), k=3, mode="ivf")
    b = _Request("nn", payload=np.zeros((2, 4)), k=3, mode="exact")
    c = _Request("nn", payload=np.zeros((2, 4)), k=3, mode="ivf")
    assert not _mergeable(a, b)
    assert _mergeable(a, c)


def test_ivf_refresher_rebuilds_off_the_serving_path():
    """The index maker keeps serving live: requests issued while the
    refresher is clustering all complete, the index gets (re)built, and
    post-build searches are served from it."""
    n, d = 512, 16
    table = _clustered_table(n, d, 8, seed=5)
    srv = KnowledgeBankServer(n, d, search_mode="ivf", ann_nlist=8,
                              ann_nprobe=4)
    srv.update(np.arange(n), table)
    refresher = srv.start_ann_refresher(rebuild_rows=64, iters=4,
                                        min_period_s=0.001)
    rng = np.random.default_rng(1)
    deadline = time.time() + 10.0
    served = 0
    while (refresher.rebuilds < 2 or srv.engine.search_stats["ivf"] == 0) \
            and time.time() < deadline:
        ids = rng.integers(0, n, (16,))
        srv.update(ids, table[ids] + 0.01)        # drives staleness up
        s, i = srv.nn_search(table[ids[:4]], 5)
        assert s.shape == (4, 5) and i.shape == (4, 5)
        served += 1
    srv.close()
    assert refresher.rebuilds >= 2, refresher.rebuilds
    assert srv.engine.search_stats["ivf"] > 0
    assert served > 0


def test_ivf_refresher_snapshots_under_donating_writes():
    """A writer streams lazy_grad and lookup calls, whose programs donate
    the table, while the refresher rebuilds from it: the refresher copies
    the table under the engine's swap lock, so every rebuild completes
    without error, searches stay valid and no donation is missed."""
    n, d = 512, 16
    table = _clustered_table(n, d, 8, seed=6)
    srv = KnowledgeBankServer(n, d, search_mode="ivf", ann_nlist=8,
                              ann_nprobe=4)
    srv.update(np.arange(n), table)
    refresher = srv.start_ann_refresher(rebuild_rows=64, iters=4,
                                        min_period_s=0.001)
    stop, errors, refresh_errors = threading.Event(), [], []

    def writer():
        rng = np.random.default_rng(6)
        try:
            while not stop.is_set():
                ids = rng.integers(0, n, 32)
                srv.lazy_grad(ids, 0.01 * rng.standard_normal(
                    (32, d)).astype(np.float32))
                rows = srv.lookup(ids)
                assert rows.shape == (32, d) and np.isfinite(rows).all()
        except Exception as e:
            errors.append(e)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    deadline = time.monotonic() + 40.0
    try:
        while refresher.rebuilds < 3 and time.monotonic() < deadline:
            s, i = srv.nn_search(table[:4] + 0.01, 5)
            assert s.shape == (4, 5) and np.isfinite(s).all()
            assert ((i >= 0) & (i < n)).all()
            if refresher.last_error is not None:
                refresh_errors.append(refresher.last_error)
    finally:
        stop.set()
        t.join(timeout=10.0)
        srv.close()
    assert not t.is_alive()
    assert not errors, errors
    assert not refresh_errors and refresher.last_error is None
    assert refresher.rebuilds >= 2, refresher.rebuilds
    assert srv.engine.donation_misses == 0
