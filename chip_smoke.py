"""On-chip smoke run: the Knowledge Bank and the CARLS trainer through their
normal entry points, on a TPU, checked against the dense reference.

  python chip_smoke.py             # one chip: phases kb, kb-correct, train
  python chip_smoke.py --chips 4   # four chips: the sharded bank only

It refuses to run anywhere but a TPU: if JAX's first device is not a TPU
(for example under ``JAX_PLATFORMS=cpu``) it exits non-zero without a
result line. Every phase raises on the first failed check, so the exit
code covers the whole run. The last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``; the
per-phase seconds printed before it include compilation and data set-up
and are not performance figures.

Phases (one chip):

- ``kb``: ``repro.launch.serve.main`` serving a 2^20 x 128 fp32 bank
  (the SIFT-1M shape of ann-benchmarks, rows rounded up to 2^20) on the
  Pallas backend in compiled mode, with 8 concurrent clients and IVF
  search (nlist 1024, nprobe 32). Requires IVF searches to have run.
- ``kb-correct``: a seeded update / lazy_grad / lookup / flush / exact and
  IVF nn_search sequence, with and without ``exclude_ids``, through a
  Pallas ``KBEngine`` and through the dense functional reference
  (``repro.core.knowledge_bank``) on the same bank.
- ``train``: ``repro.launch.train.main`` on yi-6b at published widths, two
  layers deep, five steps with bank traffic and maker refreshes.

Four chips: a ``KnowledgeBankServer`` on the sharded backend over a 2x2
host mesh with 2^22 rows (2^20 per chip); exact and sharded-IVF search
against the dense reference on one chip, and each chip's share of the
bank state.

Matmul precision: every bank score and one-hot-gather matmul, in the
Pallas kernels, the sharded ops and the dense reference, runs at
``Precision.HIGHEST`` (full fp32); the TPU default would round through
bf16.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

K = 10
LAZY_LR, ZMAX = 0.1, 3.0
KB_ROWS, KB_DIM = 1 << 20, 128
NLIST, NPROBE = 1024, 32
RECALL_FLOOR = 0.95


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# comparisons against the dense reference
# ---------------------------------------------------------------------------

def _check_rows(got, want, pending, what: str) -> None:
    """Rows without a pending gradient are a plain gather: bit-exact. Rows
    whose pending gradient was applied agree to 1e-6 of the row's
    largest magnitude."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    pending = np.asarray(pending, bool)
    clean = ~pending
    _check(np.array_equal(got[clean], want[clean]),
           f"{what}: {int((got[clean] != want[clean]).any(-1).sum())} rows "
           "without pending gradients differ from the reference gather")
    if pending.any():
        err = np.abs(got[pending] - want[pending]).max(-1)
        scale = np.abs(want[pending]).max(-1)
        worst = float((err / np.maximum(scale, 1e-30)).max())
        _check(worst <= 1e-6, f"{what}: pending rows off by {worst:.3g} "
                              "relative (limit 1e-6)")
    print(f"  {what}: {int(clean.sum())} clean rows bit-exact, "
          f"{int(pending.sum())} pending rows within 1e-6", flush=True)


def _check_exact_ids(got_s, got_i, ref_s, ref_i, what: str) -> None:
    """Top-k ids agree with the reference as sets, except where the
    reference's k-th and (k+1)-th scores tie within 1e-5 relative; then the
    result must still come from the reference's top k+1. ``ref_*`` hold
    k+1 columns."""
    import numpy as np
    got_s, got_i = np.asarray(got_s), np.asarray(got_i)
    ref_s, ref_i = np.asarray(ref_s), np.asarray(ref_i)
    ties = 0
    for b in range(got_i.shape[0]):
        if set(got_i[b]) == set(ref_i[b, :K]):
            continue
        gap = ref_s[b, K - 1] - ref_s[b, K]
        tie = gap <= 1e-5 * max(abs(float(ref_s[b, K - 1])), 1.0)
        _check(tie and set(got_i[b]) <= set(ref_i[b]),
               f"{what}: query {b} ids {sorted(got_i[b])} != reference "
               f"{sorted(ref_i[b, :K])} (k-th gap {gap:.3g})")
        ties += 1
    tol = 1e-5 * max(float(np.abs(ref_s[:, :K]).max()), 1.0)
    err = float(np.abs(got_s - ref_s[:, :K]).max())
    _check(err <= tol, f"{what}: scores off by {err:.3g} (limit {tol:.3g})")
    print(f"  {what}: ids match the reference for {got_i.shape[0]} queries "
          f"({ties} boundary ties), max score error {err:.3g}", flush=True)


def _recall(got_i, ref_i) -> float:
    import numpy as np
    got_i, ref_i = np.asarray(got_i), np.asarray(ref_i)
    return float(np.mean([len(set(got_i[b]) & set(ref_i[b, :K])) / K
                          for b in range(got_i.shape[0])]))


def _queries(table, n: int, seed: int):
    """Queries near stored rows: the neighbour lookups makers issue."""
    import numpy as np
    rng = np.random.default_rng(seed)
    rows = table[rng.integers(0, table.shape[0], n)]
    return (rows + 0.05 * rng.standard_normal(rows.shape)).astype(np.float32)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_kb() -> None:
    from repro.launch import serve
    res = serve.main([
        "--kb", "--kb-backend", "pallas", "--interpret", "false",
        "--kb-entries", str(KB_ROWS), "--kb-dim", str(KB_DIM),
        "--kb-search", "ivf", "--nlist", str(NLIST),
        "--nprobe", str(NPROBE), "--clients", "8", "--batch", "512",
        "--gen", "4"])
    _check(res["interpret"] is False,
           f"pallas backend ran with interpret={res['interpret']}")
    _check(res["search_stats"]["ivf"] > 0,
           f"no IVF search ran: {res['search_stats']}")
    print(f"  kb: search_stats={res['search_stats']} "
          f"requests={res['requests']} dispatches={res['dispatches']}",
          flush=True)


def phase_kb_correct(seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    from repro.core import knowledge_bank as kbm
    from repro.core.ann_index import clustered_bank
    from repro.core.kb_engine import KBEngine, make_kb_ops
    N, D = KB_ROWS, KB_DIM
    rng = np.random.default_rng(seed)
    table = clustered_bank(N, D, NLIST, seed=seed)
    eng = KBEngine(N, D, backend="pallas", interpret=False,
                   lazy_lr=LAZY_LR, zmax=ZMAX, search_mode="exact",
                   ann_nlist=NLIST, ann_nprobe=NPROBE)
    _check(eng.backend.interpret is False, "PallasBackend is interpreting")
    ref_update = jax.jit(kbm.kb_update)
    ref_lazy = jax.jit(partial(kbm.kb_lazy_grad, zmax=ZMAX))
    ref_lookup = jax.jit(partial(kbm.kb_lookup, lazy_lr=LAZY_LR, zmax=ZMAX))
    ref_flush = jax.jit(partial(kbm.kb_flush, lazy_lr=LAZY_LR, zmax=ZMAX))
    ref_nn = jax.jit(kbm.kb_nn_search, static_argnums=2)
    ref_nn_excl = jax.jit(lambda st, q, e: kbm.kb_nn_search(
        st, q, K + 1, exclude_ids=e))

    # fill, then overwrite a random subset
    all_ids = np.arange(N, dtype=np.int32)
    eng.update(all_ids, table)
    ref = ref_update(kbm.kb_create(N, D), jnp.asarray(all_ids),
                     jnp.asarray(table))
    upd = rng.choice(N, N // 256, replace=False).astype(np.int32)
    vals = rng.standard_normal((upd.size, D)).astype(np.float32)
    eng.update(upd, vals)
    ref = ref_update(ref, jnp.asarray(upd), jnp.asarray(vals))

    # lazy gradients on N/256 draws (duplicates included)
    gids = rng.integers(0, N, N // 256).astype(np.int32)
    grads = (0.1 * rng.standard_normal((gids.size, D))).astype(np.float32)
    eng.lazy_grad(gids, grads)
    ref = ref_lazy(ref, jnp.asarray(gids), jnp.asarray(grads))
    pending = np.zeros(N, bool)
    pending[gids] = True
    ever_pending = pending.copy()

    # lookup: half pending rows, half clean, with duplicates
    look = np.concatenate([rng.choice(gids, 256),
                           rng.choice(np.flatnonzero(~pending), 256)])
    look = rng.permutation(look).astype(np.int32)
    got = eng.lookup(look)
    want, ref = ref_lookup(ref, jnp.asarray(look))
    _check_rows(got, want, pending[look], "lookup")
    pending[look] = False
    _check(np.array_equal(eng.version_snapshot(), np.asarray(ref.version)),
           "lookup: version counters differ from the reference")

    # flush applies every remaining pending gradient
    eng.flush()
    ref = ref_flush(ref)
    _check_rows(eng.table_snapshot(), np.asarray(ref.table), ever_pending,
                "flush (whole table)")
    _check(np.array_equal(eng.version_snapshot(), np.asarray(ref.version)),
           "flush: version counters differ from the reference")

    # exact search, then with exclusions (engine and in-graph KBOps paths)
    q = _queries(table, 64, seed + 1)
    ref_s, ref_i = ref_nn(ref, jnp.asarray(q), K + 1)
    s, i = eng.nn_search(q, K, mode="exact")
    _check_exact_ids(s, i, ref_s, ref_i, "exact nn_search")
    excl = np.full((q.shape[0], 2), -1, np.int32)
    excl[:, 0] = np.asarray(ref_i)[:, 0]            # ban each top hit
    ref_xs, ref_xi = ref_nn_excl(ref, jnp.asarray(q), jnp.asarray(excl))
    s, i = eng.nn_search(q, K, mode="exact", exclude_ids=excl)
    _check(not (np.asarray(i) == excl[:, :1]).any(), "engine exclusion leaked")
    ops = make_kb_ops(backend=eng.backend)
    ks, ki = jax.jit(lambda st, qq, e: ops.nn_search(
        st, qq, K, exclude_ids=e))(eng.state, jnp.asarray(q),
                                   jnp.asarray(excl))
    _check(not (np.asarray(ki) == excl[:, :1]).any(),
           "PallasBackend exclusion leaked")
    _check_exact_ids(s, i, ref_xs, ref_xi,
                     "exact nn_search, excluded (engine)")
    _check_exact_ids(ks, ki, ref_xs, ref_xi,
                     "exact nn_search, excluded (KBOps)")

    # IVF: build the index on the chip, then recall against exact
    t0 = time.perf_counter()
    eng.rebuild_ann_index()
    print(f"  ivf index build (set-up): {time.perf_counter() - t0:.1f}s, "
          f"cap={eng.ann_index.bucket_cap}", flush=True)
    s, i = eng.nn_search(q, K, mode="ivf")
    _check(eng.search_stats["ivf"] > 0, "IVF search fell back to exact")
    rec = _recall(i, ref_i)
    _check(rec >= RECALL_FLOOR, f"IVF recall@10 {rec:.3f} < {RECALL_FLOOR}")
    s, i = eng.nn_search(q, K, mode="ivf", exclude_ids=excl)
    _check(not (np.asarray(i) == excl[:, :1]).any(), "IVF exclusion leaked")
    rec_x = _recall(i, ref_xi)
    _check(rec_x >= RECALL_FLOOR,
           f"IVF recall@10 with exclusions {rec_x:.3f} < {RECALL_FLOOR}")
    print(f"  ivf nn_search: recall@10 {rec:.4f} ({rec_x:.4f} with "
          f"exclusions) against exact, search_stats={eng.search_stats}",
          flush=True)


def phase_train() -> None:
    import numpy as np
    from repro.launch import train
    res = train.main([
        "--arch", "yi-6b", "--no-reduced", "--layers", "2", "--steps", "5",
        "--batch", "8", "--seq", "512", "--nodes", "16384",
        "--maker-every", "2", "--interpret", "false"])
    _check(res["d_model"] == 4096, f"trained at d_model={res['d_model']}")
    _check(bool(np.isfinite(res["losses"]).all()),
           f"non-finite losses {res['losses']}")
    _check(max(res["kb_pending"]) > 0, "no lazy gradient reached the bank")
    _check(res["maker_refreshes"] >= 1, "no maker refresh ran")
    print(f"  train: yi-6b {res['layers']} layers, "
          f"{res['params'] / 1e6:.1f}M params, losses {res['losses']}, "
          f"kb_pending {res['kb_pending']}, "
          f"{res['maker_refreshes']} maker refreshes", flush=True)


def phase_sharded(seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import KnowledgeBankServer
    from repro.core import knowledge_bank as kbm
    from repro.core.ann_index import clustered_bank
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.partition import DistContext
    devs = jax.devices()
    _check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    N, D = 4 * KB_ROWS, KB_DIM
    dist = DistContext(mesh=make_host_mesh((2, 2)))
    srv = KnowledgeBankServer(N, D, backend="sharded", dist=dist,
                              search_mode="exact", ann_nlist=NLIST,
                              ann_nprobe=16)
    try:
        eng = srv.engine
        per_dev = {d: 0 for d in devs}
        for leaf in jax.tree.leaves(eng.state):
            for sh in leaf.addressable_shards:
                per_dev[sh.device] += sh.data.nbytes
        total = sum(per_dev.values())
        for d, b in per_dev.items():
            stats = d.memory_stats() or {}
            print(f"  device {d.id}: {b} bytes of bank state "
                  f"({b / total:.4f} of {total}), bytes_in_use="
                  f"{stats.get('bytes_in_use', 'n/a')}", flush=True)
            _check(abs(b / total - 0.25) < 0.01,
                   f"device {d.id} holds {b / total:.3f} of the state")
        table = np.concatenate([
            clustered_bank(KB_ROWS, D, NLIST, seed=seed + s)
            for s in range(4)])
        for lo in range(0, N, KB_ROWS):
            srv.update(np.arange(lo, lo + KB_ROWS), table[lo:lo + KB_ROWS])
        ref = jax.device_put(jnp.asarray(table), devs[0])
        q = _queries(table, 64, seed + 7)
        rng = np.random.default_rng(seed)
        look = rng.integers(0, N, 512)
        _check(np.array_equal(srv.lookup(look), table[look]),
               "sharded lookup differs from the stored rows")
        ref_s, ref_i = jax.jit(lambda t, qq: jax.lax.top_k(jnp.matmul(
            qq, t.T, precision=kbm.HIGHEST), K + 1))(ref, jnp.asarray(q))
        s, i = srv.nn_search(q, K, mode="exact")
        _check_exact_ids(s, i, ref_s, ref_i, "sharded exact nn_search")
        t0 = time.perf_counter()
        eng.rebuild_ann_index()
        print(f"  sharded ivf index build (set-up): "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        s, i = srv.nn_search(q, K, mode="ivf")
        _check(eng.search_stats["ivf"] > 0, "sharded IVF fell back to exact")
        rec = _recall(i, ref_i)
        _check(rec >= RECALL_FLOOR,
               f"sharded IVF recall@10 {rec:.3f} < {RECALL_FLOOR}")
        print(f"  sharded ivf nn_search: recall@10 {rec:.4f} against exact, "
              f"search_stats={eng.search_stats}", flush=True)
    finally:
        srv.close()


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the kb, kb-correct and train phases; 4: the "
                         "sharded bank over a 2x2 mesh, nothing else")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"the repro package is missing under {SRC}")
    sys.path.insert(0, SRC)
    from repro.env import enable_compile_cache
    enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"no TPU: JAX's first device is {dev.platform!r}")
    phases = ([("sharded", phase_sharded)] if args.chips == 4 else
              [("kb", phase_kb), ("kb-correct", phase_kb_correct),
               ("train", phase_train)])
    for name, fn in phases:
        t0 = time.perf_counter()
        print(f"phase {name}: start", flush=True)
        fn()
        gc.collect()
        print(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s "
              "(set-up and compilation included)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
