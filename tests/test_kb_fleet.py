"""Fleet fail-over + live resharding (ISSUE 8), driven deterministically
through the ``FaultPlan`` fault-injection seam — no sleeps, no luck:

- Row export/import: every per-row leaf (table / version / grad
  accumulators / EMA, int8 scale+offset side-cars) round-trips
  bit-identically — the primitive both replica fill and resharding stand
  on.
- Fail-over: a partition killed mid-stream (in-process ``FaultPlan``;
  SIGKILL of a real serve.py member in the slow variant) is replaced by
  its promoted standby, and the surviving fleet is BIT-identical to a
  never-failed reference — including a hypothesis property over random op
  streams with randomly placed kills and dropped acks: an acknowledged
  write is never lost.
- Resharding: ``reshard(P -> P+1)`` moves exactly the
  ``PartitionMap``-predicted id set, every moved row round-trips every
  leaf bit-identically (fp32 and int8, pending lazy grads included), the
  logical bank is unchanged (snapshot + nn_search before == after), and
  ops issued concurrently with the reshard land on the correct owner on
  both sides of the cutover.
- The previously-untested failure seams this PR builds on:
  ``SocketTransport``'s capped-exponential backoff schedule
  (timing-mocked), partial fan-out completion when a partition dies
  mid-``nn_search``, and ``KBServerClosedError`` propagation through the
  router.
"""
import os
import re
import select
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (FaultPlan, FaultyTransport, InProcessTransport,
                        KBPartitionDownError, KBRouter, KnowledgeBankServer,
                        PartitionMap, SocketTransport, TransportError,
                        connect_kb)
from repro.core import kb_protocol as kbp

N, D = 192, 8
ROOT = os.path.join(os.path.dirname(__file__), "..")


def _table(n, d, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _fleet(n, d, parts, table, *, plan=None, standby_for=None, **srv_kw):
    """P partition servers filled from ONE global table + a router.
    ``plan`` wraps partition 0's transport in a ``FaultyTransport``;
    ``standby_for`` additionally attaches a standby to that partition,
    made bit-identical by replaying the primary's fill (fill=False skips
    the export/import stream so it does not consume fault-plan indices)."""
    pmap = PartitionMap(n, parts)
    servers = []
    transports = []
    for p in range(parts):
        s = KnowledgeBankServer(int(pmap.counts[p]), d, **srv_kw)
        s.update(np.arange(int(pmap.counts[p])), table[pmap.global_ids(p)])
        servers.append(s)
        t = InProcessTransport(s, partition=f"{p}/{parts}")
        if plan is not None and p == 0:
            t = FaultyTransport(t, plan)
        transports.append(t)
    router = KBRouter(transports, pmap=pmap)
    if standby_for is not None:
        p = standby_for
        sb = KnowledgeBankServer(int(pmap.counts[p]), d, **srv_kw)
        sb.update(np.arange(int(pmap.counts[p])), table[pmap.global_ids(p)])
        servers.append(sb)
        router.attach_standby(p, InProcessTransport(sb), fill=False)
    return pmap, servers, router


def _close(servers, router=None):
    if router is not None:
        router.close()
    for s in servers:
        s.close()


# ---------------------------------------------------------------------------
# row export/import: the replica-fill / reshard primitive
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("storage", ["fp32", "int8"])
def test_export_import_roundtrip_every_leaf(storage):
    """export_rows -> import_rows into a fresh bank reproduces every
    per-row leaf bit-identically — table, version, the PENDING lazy-grad
    accumulators, EMA, and (int8) the scale/offset side-cars."""
    src = KnowledgeBankServer(32, D, storage=storage)
    dst = KnowledgeBankServer(32, D, storage=storage)
    try:
        rng = np.random.default_rng(3)
        src.update(np.arange(32), rng.normal(size=(32, D)).astype(np.float32),
                   src_step=5)
        src.lazy_grad(np.arange(0, 32, 2),
                      rng.normal(size=(16, D)).astype(np.float32))
        ids = np.arange(32)
        leaves = src.export_rows(ids)
        assert {"table", "version", "grad_sum", "grad_cnt",
                "grad_sqnorm", "norm_ema"} <= set(leaves)
        if storage == "int8":
            assert {"scale", "offset"} <= set(leaves)
        dst.import_rows(ids, leaves)
        back = dst.export_rows(ids)
        assert set(back) == set(leaves)
        for k in leaves:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(leaves[k]))
        # the pending grads MOVED: flushing both produces the same table
        src.flush()
        dst.flush()
        np.testing.assert_array_equal(src.table_snapshot(),
                                      dst.table_snapshot())
    finally:
        src.close()
        dst.close()


def test_import_rows_rejects_leaf_set_mismatch():
    """An fp32 export cannot land in an int8 bank (and vice versa): the
    leaf sets differ, and silently dropping side-cars would corrupt."""
    src = KnowledgeBankServer(8, D)
    dst = KnowledgeBankServer(8, D, storage="int8")
    try:
        leaves = src.export_rows(np.arange(8))
        with pytest.raises(ValueError, match="leaf set"):
            dst.import_rows(np.arange(8), leaves)
    finally:
        src.close()
        dst.close()


# ---------------------------------------------------------------------------
# fail-over: deterministic kills through the FaultPlan seam
# ---------------------------------------------------------------------------

def test_failover_promotes_standby_bit_identical():
    """Partition 0 dies mid-stream (every transport request fails from a
    fixed index on); the router drains the write tail, promotes the
    standby, re-issues the failed request — and the healed fleet is
    bit-identical to a never-failed reference on snapshot AND lookups."""
    table = _table(N, D)
    _, ref_srvs, ref = _fleet(N, D, 2, table)
    plan = FaultPlan(kill_after_requests=6)
    _, srvs, router = _fleet(N, D, 2, table, plan=plan, standby_for=0)
    try:
        rng = np.random.default_rng(1)
        for _ in range(12):
            ids = rng.integers(0, N, 5)
            g = rng.normal(size=(5, D)).astype(np.float32)
            for r in (ref, router):
                r.lazy_grad(ids, g)
                r.lookup(ids, trainer_step=1)
        assert router.router_metrics["promotions"] == 1
        assert plan.faults >= 1
        np.testing.assert_array_equal(ref.table_snapshot(),
                                      router.table_snapshot())
        np.testing.assert_array_equal(ref.lookup(np.arange(N)),
                                      router.lookup(np.arange(N)))
        assert router.stats()["router"]["promotions"] == 1
    finally:
        _close(ref_srvs, ref)
        _close(srvs, router)


def test_failover_without_standby_fails_fast():
    """No standby -> the old contract: KBPartitionDownError names the dead
    member, ids owned by the survivor keep serving."""
    table = _table(N, D)
    plan = FaultPlan(kill_after_requests=0)
    pmap, srvs, router = _fleet(N, D, 2, table, plan=plan)
    try:
        with pytest.raises(KBPartitionDownError) as ei:
            router.lookup(pmap.global_ids(0)[:4])
        assert ei.value.partition == 0
        assert "injected fault" in str(ei.value)
        ok = pmap.global_ids(1)[:4]
        np.testing.assert_allclose(router.lookup(ok), table[ok], rtol=1e-5)
    finally:
        _close(srvs, router)


def test_kb_server_closed_error_names_itself_through_router():
    """KBServerClosedError (the in-process analogue of a dead peer) must
    surface as KBPartitionDownError carrying the original class name —
    supervisors distinguish 'server shut down' from 'connection lost'."""
    table = _table(N, D)
    pmap, srvs, router = _fleet(N, D, 2, table)
    try:
        srvs[1].close()
        with pytest.raises(KBPartitionDownError) as ei:
            router.lookup(pmap.global_ids(1)[:4])
        assert ei.value.partition == 1
        assert "KBServerClosedError" in str(ei.value)
    finally:
        _close(srvs, router)


def test_partial_fanout_completes_when_partition_dies_mid_nn():
    """A partition dying inside an nn_search fan-out must not cancel the
    sub-requests the other members already took: the router completes
    every sub-request BEFORE re-raising (writes elsewhere are never
    half-applied), and the error still names the dead member."""
    table = _table(N, D)
    plan = FaultPlan(kill_after_requests=0)
    pmap, srvs, router = _fleet(N, D, 2, table, plan=plan)
    try:
        before = int(srvs[1].metrics["requests"])
        q = np.zeros((2, D), np.float32)
        with pytest.raises(KBPartitionDownError) as ei:
            router.nn_search(q, k=3)
        assert ei.value.partition == 0
        # the healthy member EXECUTED its shortlist sub-request
        assert int(srvs[1].metrics["requests"]) == before + 1
    finally:
        _close(srvs, router)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(0, 24), st.booleans())
def test_acked_writes_never_lost_across_promotion(seed, kill_at, drop_ack):
    """Hypothesis property (the acceptance criterion): over random op
    streams with a randomly placed permanent kill — and optionally a
    dropped ack, the at-least-once hazard where the primary EXECUTED but
    the response was lost — every acknowledged write survives promotion:
    the healed fleet is bit-identical to a never-failed reference."""
    table = _table(N, D, seed=2)
    _, ref_srvs, ref = _fleet(N, D, 2, table)
    drops = (kill_at - 3,) if (drop_ack and kill_at >= 3) else ()
    plan = FaultPlan(kill_after_requests=kill_at, drop_responses=drops)
    _, srvs, router = _fleet(N, D, 2, table, plan=plan, standby_for=0)
    try:
        rng = np.random.default_rng(seed)
        for _ in range(16):
            kind = int(rng.integers(3))
            ids = rng.integers(0, N, int(rng.integers(1, 6)))
            if kind == 0:
                a = ref.lookup(ids, trainer_step=1)
                b = router.lookup(ids, trainer_step=1)
                np.testing.assert_array_equal(a, b)
            elif kind == 1:
                v = rng.normal(size=(ids.size, D)).astype(np.float32)
                ref.update(ids, v, src_step=2)
                router.update(ids, v, src_step=2)
            else:
                g = rng.normal(size=(ids.size, D)).astype(np.float32)
                ref.lazy_grad(ids, g)
                router.lazy_grad(ids, g)
        ref.flush()
        router.flush()
        np.testing.assert_array_equal(ref.table_snapshot(),
                                      router.table_snapshot())
        np.testing.assert_array_equal(ref.lookup(np.arange(N)),
                                      router.lookup(np.arange(N)))
    finally:
        _close(ref_srvs, ref)
        _close(srvs, router)


def test_attach_standby_validates_geometry_and_duplicates():
    table = _table(N, D)
    pmap, srvs, router = _fleet(N, D, 2, table)
    extra = []
    try:
        wrong = KnowledgeBankServer(int(pmap.counts[0]) + 1, D)
        extra.append(wrong)
        with pytest.raises(ValueError, match="rows"):
            router.attach_standby(0, InProcessTransport(wrong))
        mislabeled = KnowledgeBankServer(int(pmap.counts[0]), D)
        extra.append(mislabeled)
        with pytest.raises(ValueError, match="partition"):
            router.attach_standby(
                0, InProcessTransport(mislabeled, partition="1/2"))
        ok = KnowledgeBankServer(int(pmap.counts[0]), D)
        extra.append(ok)
        router.attach_standby(0, InProcessTransport(ok))
        assert router.standby_status() == [True, False]
        dup = KnowledgeBankServer(int(pmap.counts[0]), D)
        extra.append(dup)
        with pytest.raises(ValueError, match="already"):
            router.attach_standby(0, InProcessTransport(dup))
    finally:
        _close(srvs + extra, router)


def test_lost_standby_is_dropped_not_fatal():
    """A standby dying under the tee demotes it (standbys_lost) but the
    primary keeps serving — losing the spare must never fail the op."""
    table = _table(N, D)
    pmap, srvs, router = _fleet(N, D, 2, table)
    sb = KnowledgeBankServer(int(pmap.counts[0]), D)
    try:
        router.attach_standby(0, InProcessTransport(sb), fill=False)
        sb.close()                          # the SPARE dies, not the primary
        ids = pmap.global_ids(0)[:4]
        v = np.ones((4, D), np.float32)
        router.update(ids, v)               # tee fails -> standby dropped
        assert router.router_metrics["standbys_lost"] == 1
        assert router.standby_status() == [False, False]
        np.testing.assert_array_equal(router.lookup(ids), v)
    finally:
        _close(srvs + [sb], router)


def test_promotion_auto_reattaches_spare_survives_second_kill():
    """After a promotion the router automatically fills the next COLD
    spare from the new primary (under the same slot lock) and attaches it
    as the fresh standby — so a SECOND kill promotes again, and across
    both kills every acknowledged write survives: the twice-healed fleet
    is bit-identical to a never-failed reference."""
    table = _table(N, D)
    _, ref_srvs, ref = _fleet(N, D, 2, table)
    plan1, plan2 = FaultPlan(), FaultPlan()
    pmap, srvs, router = _fleet(N, D, 2, table, plan=plan1)
    extra = []
    try:
        # warm standby (pre-filled, fill=False) wrapped in its OWN plan so
        # the PROMOTED primary can be killed deterministically later
        sb = KnowledgeBankServer(int(pmap.counts[0]), D)
        sb.update(np.arange(int(pmap.counts[0])), table[pmap.global_ids(0)])
        extra.append(sb)
        router.attach_standby(
            0, FaultyTransport(InProcessTransport(sb), plan2), fill=False)
        # one cold spare, deliberately EMPTY: only the auto-attach fill
        # can make it bit-identical to the promoted primary
        sp = KnowledgeBankServer(int(pmap.counts[0]), D)
        extra.append(sp)
        router.add_spare(0, InProcessTransport(sp))
        assert router.spare_status() == [1, 0]

        rng = np.random.default_rng(21)

        def acked_traffic(rounds):
            for _ in range(rounds):
                ids = rng.integers(0, N, 6)
                v = rng.normal(size=(6, D)).astype(np.float32)
                ref.update(ids, v, src_step=1)
                router.update(ids, v, src_step=1)
                g = rng.normal(size=(6, D)).astype(np.float32)
                ref.lazy_grad(ids, g)
                router.lazy_grad(ids, g)

        acked_traffic(4)
        plan1.kill_after_requests = plan1.requests  # primary 0 dies NOW
        acked_traffic(4)                            # trips promotion #1
        assert router.router_metrics["promotions"] == 1
        assert router.router_metrics["spares_attached"] == 1
        assert router.standby_status() == [True, False]
        assert router.spare_status() == [0, 0]
        plan2.kill_after_requests = plan2.requests  # promoted one dies
        acked_traffic(4)                            # trips promotion #2
        assert router.router_metrics["promotions"] == 2
        assert router.standby_status() == [False, False]  # pool exhausted
        ref.flush()
        router.flush()
        np.testing.assert_array_equal(ref.table_snapshot(),
                                      router.table_snapshot())
        np.testing.assert_array_equal(ref.lookup(np.arange(N)),
                                      router.lookup(np.arange(N)))
        assert router.stats()["router"]["spares_attached"] == 1
    finally:
        _close(ref_srvs, ref)
        _close(srvs + extra, router)


def test_add_spare_validates_geometry_and_counts():
    table = _table(N, D)
    pmap, srvs, router = _fleet(N, D, 2, table)
    extra = []
    try:
        wrong = KnowledgeBankServer(int(pmap.counts[0]) + 1, D)
        extra.append(wrong)
        with pytest.raises(ValueError, match="spare"):
            router.add_spare(0, InProcessTransport(wrong))
        ok = KnowledgeBankServer(int(pmap.counts[1]), D)
        extra.append(ok)
        router.add_spare(1, InProcessTransport(ok))
        assert router.spare_status() == [0, 1]
        assert router.stats()["router"]["spares"] == 1
    finally:
        _close(srvs + extra, router)


# ---------------------------------------------------------------------------
# SocketTransport backoff schedule (timing-mocked)
# ---------------------------------------------------------------------------

def test_socket_backoff_schedule_capped_exponential(monkeypatch):
    """The retry schedule is min(cap, base * 2**(attempt-1)) with jitter:
    mock the clock and the jitter and assert the EXACT sleep sequence —
    the doc'd contract, previously untested."""
    import repro.core.kb_transport as kbt
    sleeps = []
    real_time = time

    class _FakeTime:
        def __getattr__(self, name):
            return getattr(real_time, name)

        def sleep(self, s):
            sleeps.append(round(float(s), 6))

    monkeypatch.setattr(kbt, "time", _FakeTime())
    monkeypatch.setattr(kbt.random, "uniform", lambda a, b: 1.0)
    srv = KnowledgeBankServer(16, 4)
    ts = kbt.KBTransportServer(srv)
    t = SocketTransport("127.0.0.1", ts.port, max_retries=3,
                        reconnect_backoff_s=0.05,
                        reconnect_backoff_cap_s=0.08)
    try:
        ts.close()
        srv.close()
        sleeps.clear()                      # only the retry loop from here
        with pytest.raises(TransportError, match="after 4 attempts"):
            t.request(kbp.StatsRequest())
        assert sleeps == [0.05, 0.08, 0.08]     # 0.05*2^k capped at 0.08
    finally:
        t.close()


# ---------------------------------------------------------------------------
# live resharding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("storage", ["fp32", "int8"])
def test_reshard_moves_exact_predicted_set_bit_identical(storage):
    """reshard(2 -> 3) moves exactly the ids the ring predicts, every
    moved row round-trips every leaf bit-identically (pending lazy grads
    included), and the LOGICAL bank is unchanged: snapshot and nn_search
    answer identically before and after."""
    table = _table(N, D, seed=7)
    pmap, srvs, router = _fleet(N, D, 2, table, storage=storage)
    srv3 = None
    try:
        rng = np.random.default_rng(11)
        up = rng.integers(0, N, 64)
        router.update(up, rng.normal(size=(64, D)).astype(np.float32),
                      src_step=3)
        lg = rng.integers(0, N, 40)
        router.lazy_grad(lg, rng.normal(size=(40, D)).astype(np.float32))

        new_pmap = PartitionMap(N, 3)
        moved = np.flatnonzero(new_pmap.owner != pmap.owner)
        pre = {}
        for p in range(2):
            sel = moved[pmap.owner[moved] == p]
            leaves = srvs[p].export_rows(pmap.local[sel])
            for j, g in enumerate(sel):
                pre[int(g)] = {k: np.asarray(v)[j]
                               for k, v in leaves.items()}
        snap_before = router.table_snapshot()
        q = rng.normal(size=(4, D)).astype(np.float32)
        nn_before = router.nn_search(q, k=6)

        srv3 = KnowledgeBankServer(moved.size, D, storage=storage)
        res = router.reshard(InProcessTransport(srv3), chunk_rows=16)
        assert res["moved"] == moved.size == int(new_pmap.counts[2])
        assert res["partitions"] == 3

        post = srv3.export_rows(np.arange(moved.size))
        assert set(post) == set(next(iter(pre.values())))
        for j, g in enumerate(moved):       # srv3 row j IS global moved[j]
            for k in post:
                np.testing.assert_array_equal(np.asarray(post[k])[j],
                                              pre[int(g)][k])
        np.testing.assert_array_equal(router.table_snapshot(), snap_before)
        nn_after = router.nn_search(q, k=6)
        if storage == "fp32":
            # exact search: per-member top-(k+E) merged is the global
            # top-k whatever the partition layout — bit-identical
            np.testing.assert_array_equal(nn_after[1], nn_before[1])
            np.testing.assert_allclose(nn_after[0], nn_before[0], rtol=0)
        else:
            # int8 shortlists are selected with QUANTIZED scores per
            # member, so the candidate set is partition-dependent by
            # design; row state is already proven bit-identical above
            assert nn_after[1].shape == nn_before[1].shape
            assert np.all((nn_after[1] >= 0) & (nn_after[1] < N))
        # pending grads flushed AFTER the move apply on the new owner
        router.flush()
        assert router.stats()["router"]["reshards"] == 1
    finally:
        _close(srvs + ([srv3] if srv3 else []), router)


def test_reshard_concurrent_traffic_lands_on_correct_owner():
    """Ops racing the reshard: writes acknowledged during the copy are
    never lost (dirty re-copy at cutover), post-cutover ops land on the
    NEW member's bank, and pre-cutover rows on surviving members are
    untouched. The writer thread never sleeps — the cutover's slot-lock
    exclusion is the synchronization, not timing."""
    table = _table(N, D, seed=5)
    pmap, srvs, router = _fleet(N, D, 2, table)
    new_pmap = PartitionMap(N, 3)
    moved = np.flatnonzero(new_pmap.owner != pmap.owner)
    stable = np.flatnonzero(new_pmap.owner == pmap.owner)
    g_m, g_s = int(moved[0]), int(stable[0])
    acked = {"m": 0.0, "s": 0.0}
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            i += 1
            v = np.full((1, D), float(i), np.float32)
            router.update([g_m], v)
            acked["m"] = float(i)
            router.update([g_s], v)
            acked["s"] = float(i)

    th = threading.Thread(target=writer)
    srv3 = KnowledgeBankServer(moved.size, D)
    try:
        th.start()
        res = router.reshard(InProcessTransport(srv3), chunk_rows=8)
        stop.set()
        th.join(timeout=60)
        assert not th.is_alive()
        assert res["moved"] == moved.size
        # last ACKED value is what the router serves for both ids
        np.testing.assert_array_equal(
            router.lookup([g_m]), np.full((1, D), acked["m"], np.float32))
        np.testing.assert_array_equal(
            router.lookup([g_s]), np.full((1, D), acked["s"], np.float32))
        # post-cutover write to a moved id lands PHYSICALLY on the new
        # member; the stable id stays on its old owner
        router.update([g_m], np.full((1, D), 999.0, np.float32))
        row = srv3.lookup([int(new_pmap.local[g_m])])
        np.testing.assert_array_equal(row, np.full((1, D), 999.0,
                                                   np.float32))
        p_s = int(pmap.owner[g_s])
        row_s = srvs[p_s].lookup([int(pmap.local[g_s])])
        np.testing.assert_array_equal(
            row_s, np.full((1, D), acked["s"], np.float32))
    finally:
        stop.set()
        _close(srvs + [srv3], router)


def test_reshard_rejects_missized_member():
    table = _table(N, D)
    _, srvs, router = _fleet(N, D, 2, table)
    bad = KnowledgeBankServer(7, D)
    try:
        with pytest.raises(ValueError, match="--kb-join 2/3"):
            router.reshard(InProcessTransport(bad))
    finally:
        _close(srvs + [bad], router)


def test_reshard_then_failover_compose():
    """The two fleet operations compose: grow 2 -> 3, then kill the NEW
    member and promote a standby attached after the reshard — the healed
    fleet still answers bit-identically to a never-resharded reference."""
    table = _table(N, D, seed=9)
    _, ref_srvs, ref = _fleet(N, D, 2, table)
    pmap, srvs, router = _fleet(N, D, 2, table)
    new_pmap = PartitionMap(N, 3)
    moved = np.flatnonzero(new_pmap.owner != pmap.owner)
    extra = []
    try:
        plan = FaultPlan()                  # armed AFTER setup traffic
        srv3 = KnowledgeBankServer(moved.size, D)
        extra.append(srv3)
        router.reshard(FaultyTransport(InProcessTransport(srv3), plan))
        sb = KnowledgeBankServer(moved.size, D)
        extra.append(sb)
        router.attach_standby(2, InProcessTransport(sb), fill=True)
        plan.kill_after_requests = plan.requests    # p2 dies NOW
        ids = moved[:5]
        v = np.full((5, D), 42.0, np.float32)
        ref.update(ids, v)
        router.update(ids, v)               # trips the kill -> promotion
        assert router.router_metrics["promotions"] == 1
        np.testing.assert_array_equal(ref.table_snapshot(),
                                      router.table_snapshot())
        np.testing.assert_array_equal(ref.lookup(np.arange(N)),
                                      router.lookup(np.arange(N)))
    finally:
        _close(ref_srvs, ref)
        _close(srvs + extra, router)


# ---------------------------------------------------------------------------
# connect_kb replica syntax
# ---------------------------------------------------------------------------

def test_connect_kb_third_leg_joins_spare_pool_over_wire():
    """``"p|s|c"`` legs: primary + standby + COLD spare, all over TCP. The
    spare is geometry-checked and claimed (v4 ``AttachSpare``) on
    admission, so a second router claiming it for another slot is
    refused."""
    from repro.core import KBTransportServer
    table = _table(N, D)
    servers, tsrvs = [], []
    try:
        legs = []
        for label in ("0/1", "", ""):
            s = KnowledgeBankServer(N, D)
            s.update(np.arange(N), table)
            tsrv = KBTransportServer(s, partition=label)
            servers.append(s)
            tsrvs.append(tsrv)
            legs.append(f"127.0.0.1:{tsrv.port}")
        router = connect_kb("|".join(legs))
        try:
            assert router.standby_status() == [True]
            assert router.spare_status() == [1]
            assert tsrvs[2].spare_claim == "0/1"
            got = router.lookup(np.arange(N))
            np.testing.assert_array_equal(got, table)
            # the claim is sticky: a claim for a DIFFERENT slot is
            # refused (spare_conflict), re-claiming the same slot is
            # idempotent
            conflicting = SocketTransport("127.0.0.1", tsrvs[2].port)
            with pytest.raises(kbp.RemoteKBError, match="spare_conflict"):
                conflicting.request(kbp.AttachSpareRequest("1/2"))
            conflicting.request(kbp.AttachSpareRequest("0/1"))
            conflicting.close()
        finally:
            router.close()
    finally:
        for tsrv in tsrvs:
            tsrv.close()
        _close(servers)


def test_chip_plan_one_serving_process_per_chip():
    """The fleet launcher's chip layout: each serving process owns one
    chip, a layout with more servers than chips fails before booting, and
    client-only processes (maker workers) are pinned to the CPU."""
    from repro.launch.fleet import ChipPlan
    plan = ChipPlan(2)
    envs = [plan.serving_env({}) for _ in range(2)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 2
    assert all("JAX_PLATFORMS" not in e for e in envs)
    with pytest.raises(RuntimeError, match="chip of its own"):
        plan.serving_env({})
    with pytest.raises(RuntimeError, match="chip of its own"):
        ChipPlan(2).require(3)
    ChipPlan(0).require(3)                  # no chips: all on the CPU
    assert ChipPlan(0).serving_env({})["JAX_PLATFORMS"] == "cpu"
    assert ChipPlan.client_env({"JAX_PLATFORMS": "tpu"})[
        "JAX_PLATFORMS"] == "cpu"


# ---------------------------------------------------------------------------
# separate-process end-to-end: SIGKILL a real fleet member
# ---------------------------------------------------------------------------

def _boot_serve(extra, name):
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--kb",
         "--kb-entries", "256", "--kb-dim", "16",
         "--listen", "127.0.0.1:0", "--serve-seconds", "600", *extra],
        env=_env(), cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    lines = []
    deadline = time.time() + 300
    while True:
        if time.time() > deadline:
            proc.kill()
            raise RuntimeError(f"{name} never listened:\n" + "".join(lines))
        ready, _, _ = select.select([proc.stdout], [], [], 5.0)
        if not ready:
            assert proc.poll() is None, f"{name} died:\n" + "".join(lines)
            continue
        line = proc.stdout.readline()
        assert line, f"{name} died:\n" + "".join(lines)
        lines.append(line)
        m = re.search(r"listening on [\d.]+:(\d+)", line)
        if m:
            return proc, int(m.group(1))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


@pytest.mark.slow
def test_sigkill_member_promoted_standby_zero_acked_loss():
    """ISSUE 8 acceptance, the real-process variant: a fleet member
    SIGKILLed under live traffic is replaced by its --replica-of standby
    with zero acknowledged-write loss, asserted bit-identically against
    the values the client had acked."""
    procs = []
    router = None
    try:
        p0, port0 = _boot_serve(["--kb-join", "0/2"], "p0")
        procs.append(p0)
        p1, port1 = _boot_serve(["--kb-join", "1/2"], "p1")
        procs.append(p1)
        s0, sport0 = _boot_serve(
            ["--kb-join", "0/2", "--replica-of", f"127.0.0.1:{port0}"],
            "s0")
        procs.append(s0)
        router = connect_kb(
            f"127.0.0.1:{port0}|127.0.0.1:{sport0},127.0.0.1:{port1}",
            max_retries=1, reconnect_backoff_s=0.01)
        n = router.num_entries
        want = _table(n, router.dim, seed=13)
        router.update(np.arange(n), want, src_step=1)   # acked everywhere
        p0.send_signal(signal.SIGKILL)                  # member 0 dies
        p0.wait(timeout=60)
        got = router.lookup(np.arange(n))               # forces promotion
        np.testing.assert_array_equal(got, want)        # zero acked loss
        assert router.router_metrics["promotions"] == 1
        v2 = np.full((4, router.dim), 7.0, np.float32)
        router.update(np.arange(4), v2)                 # healed fleet
        np.testing.assert_array_equal(router.lookup(np.arange(4)), v2)
    finally:
        if router is not None:
            router.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
