"""CPU checks of the comparison that decides ``correct``, at a size a
test run can hold. They skip the benchmark's look for a chip and drive
the rest of a run:

- the program passes its limits, and the control (the reference in the
  next precision below the configuration's, ``Precision.HIGH``) fails at
  least one of them;
- with the timed path broken underneath, ``correct`` comes out false,
  once for each fault the cell can have.

  JAX_PLATFORMS=cpu python -m pytest -q bench/test_correct.py
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402

SEED = 2 ** 31 + 7
SMALL = {"rows": 4096, "dim": 128, "mixture": {"centers": 16, "noise": 0.15},
         "server": {"backend": "pallas", "lazy_lr": 0.1, "zmax": 3.0,
                    "search_mode": "exact", "ann_nlist": 16,
                    "ann_nprobe": 4}}
MIXES = {
    "kb-sift1m-ycsbb-zipf": {"clients": 4, "ids_per_request": 16,
                             "check_share": 0.3},
    "kb-sift1m-nn-ivf": {"makers": 3, "queries_per_request": 8,
                         "batches_per_maker": 16, "check_share": 0.5},
}


def _run(cell, tamper=None, control=False):
    return run.run_cell(cell, SEED, 2.0, False, require_tpu=False,
                        cfg_overrides=SMALL, mix_overrides=MIXES[cell],
                        tamper=tamper, control=control)


def _fails(value, check):
    return not run._passes(value, check["cmp"], check["limit"])


@pytest.mark.parametrize("cell", sorted(MIXES))
def test_program_passes_and_control_fails(cell):
    res = _run(cell, control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert any(_fails(v, res["checks"][k])
               for k, v in res["control"].items()), res["control"]


# -- faults of the point path ------------------------------------------------

def _skip_writes(op, orig):
    """A write that returns the state unchanged."""
    return (lambda ids, grads: None) if op == "lazy_grad" else orig


def _half_writes(op, orig):
    """Half of each write batch left out."""
    if op != "lazy_grad":
        return orig

    def half(ids, grads):
        n = max(1, len(np.asarray(ids).reshape(-1)) // 2)
        return orig(np.asarray(ids).reshape(-1)[:n],
                    np.asarray(grads).reshape(-1, np.shape(grads)[-1])[:n])
    return half


def _altered_rows(op, orig):
    """Each served row altered where it is produced."""
    if op != "lookup":
        return orig

    def altered(ids):
        rows = np.array(orig(ids))
        rows[..., 0] += 1e-3 * np.abs(rows).max()
        return rows
    return altered


def _dispatcher_half_run(op, orig):
    """The dispatcher drops the second half of each coalesced write run
    on its way to the engine, after every request was acknowledged."""
    if op != "lazy_grad":
        return orig

    def half(ids, grads):
        n = max(1, len(ids) // 2)
        return orig(ids[:n], grads[:n])
    return half


def _dispatcher_mispaired(op, orig):
    """The dispatcher pairs each id of a write run with the next request's
    gradient row."""
    if op != "lazy_grad":
        return orig
    return lambda ids, grads: orig(ids, np.roll(grads, 1, axis=0))


_dispatcher_half_run.where = _dispatcher_mispaired.where = "dispatcher"


@pytest.mark.parametrize("fault", [_skip_writes, _half_writes,
                                   _altered_rows, _dispatcher_half_run,
                                   _dispatcher_mispaired])
def test_point_fault_is_not_correct(fault):
    res = _run("kb-sift1m-ycsbb-zipf", tamper=fault)
    assert not res["correct"]
    if getattr(fault, "where", "") == "dispatcher":
        # the reference replays the log, so only the pairing check sees it
        assert _fails(res["checks"]["write_pairs_unmatched"]["value"],
                      res["checks"]["write_pairs_unmatched"]), res["checks"]


# -- faults of the nearest-neighbour path ------------------------------------

def _shifted_ids(op, orig):
    """Each returned id altered where it is produced."""
    if op != "nn_search":
        return orig

    def shifted(queries, k, **kw):
        scores, ids = orig(queries, k, **kw)
        return scores, (np.asarray(ids) + 1) % SMALL["rows"]
    return shifted


def _half_queries(op, orig):
    """Half of the query batch left out: its replies copied from the
    half that was searched."""
    if op != "nn_search":
        return orig

    def half(queries, k, **kw):
        q = np.asarray(queries)
        n = max(1, q.shape[0] // 2)
        scores, ids = orig(q[:n], k, **kw)
        rep = np.arange(q.shape[0]) % n
        return np.asarray(scores)[rep], np.asarray(ids)[rep]
    return half


@pytest.mark.parametrize("fault", [_shifted_ids, _half_queries])
def test_nn_fault_is_not_correct(fault):
    assert not _run("kb-sift1m-nn-ivf", tamper=fault)["correct"]


def test_nn_stage2_skipping_partial_chunks_is_not_correct(monkeypatch):
    """IVF stage 2 skips each probed bucket's last, partly filled chunk:
    an off-by-one in the occupied-chunk count. Only recall sees it."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import nn_search_ivf
    plan = nn_search_ivf.ivf_chunk_plan

    def floor_plan(probes, occ, cpb, lb):
        return plan(probes, jnp.asarray(occ, jnp.int32) // lb * lb, cpb, lb)

    monkeypatch.setattr(nn_search_ivf, "ivf_chunk_plan", floor_plan)
    jax.clear_caches()
    try:
        res = _run("kb-sift1m-nn-ivf")
    finally:
        jax.clear_caches()
    assert not res["correct"]
    assert _fails(res["checks"]["recall_at_k"]["value"],
                  res["checks"]["recall_at_k"]), res["checks"]
