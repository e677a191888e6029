"""Closed-loop nearest-neighbour traffic on the Knowledge Bank server:
each maker thread calls ``nn_search`` with a fixed batch of queries from
the bank's own mixture, waits for the reply, works on it for a think time,
and calls again. There are no writes. The IVF index is built once in
set-up by the server's index maker.

The think times are exponential with the mix's mean: every seed gives
each maker the same set of them (the distribution's quantiles), in an
order of its own. Makers with no think time lock into two fixed groups
that take turns at the dispatcher, and the split that the first dispatch
happens to make sets the tail of the whole run.

``correct``: a sample of the replies, drawn from the seed before the
window, against an exact float32 (``Precision.HIGHEST``) search of the
seed's bank: recall@k of the returned ids, the gap between each returned
score and the true float64 inner product of its row, and whether every
reply is a well-formed top-k (valid, distinct ids, scores not rising).
"""
from __future__ import annotations

import time

import numpy as np

import bank
import kbserve
import reference


class Generator:
    def __init__(self, cfg: dict, mix: dict, seed: int, tamper=None,
                 control: bool = False):
        self.cfg, self.mix, self.seed, self.tamper = cfg, mix, seed, tamper
        self.with_control = control
        self.dim = cfg["dim"]

    def setup(self) -> None:
        cfg, mix = self.cfg, self.mix
        m = cfg["mixture"]
        q = mix["queries_per_request"]
        L = mix["batches_per_maker"]
        self.plans = []
        for c in range(mix["makers"]):
            qs = np.asarray(bank.mixture_queries(
                L * q, self.dim, m["centers"], m["noise"], self.seed, c))
            rng = np.random.default_rng([self.seed, 3, c])
            check = rng.random(L) < mix["check_share"]
            think = rng.permutation(-np.log1p(-(np.arange(L) + 0.5) / L)
                                    * mix["think_ms_mean"] * 1e-3)
            self.plans.append({"queries": qs.reshape(L, q, self.dim),
                               "check": check, "think_s": think})
        self.server = kbserve.build_server(cfg)
        kbserve.fill(self.server, cfg, self.seed)
        refresher = self.server.start_ann_refresher()
        while self.server.engine.ann_index is None:
            if refresher.last_error is not None or not refresher.is_alive():
                raise RuntimeError("IVF index build failed") \
                    from refresher.last_error
            time.sleep(0.01)
        idx = self.server.engine.ann_index
        self.centroids = np.asarray(idx.centroids)
        self.log = kbserve.EngineLog()
        kbserve.observe(self.server.engine, self.log, ops=("nn_search",),
                        tamper=self.tamper)
        # every batch a coalesced run can have: 1 to ``makers`` requests
        for n in range(1, mix["makers"] + 1):
            self.server.nn_search(np.zeros((n * q, self.dim), np.float32),
                                  mix["k"], mode=mix["mode"])

    def _maker(self, c: int, stop_at: float):
        import jax
        plan, mix = self.plans[c], self.mix
        rec = self.records[c]
        j = 0
        while True:
            t = time.perf_counter()
            if t >= stop_at:
                return
            k = j % len(plan["check"])
            ok = True
            with jax.profiler.TraceAnnotation("bench.nn_search"):
                try:
                    scores, ids = self.server.nn_search(
                        plan["queries"][k], mix["k"], mode=mix["mode"])
                except Exception as e:          # counted as failed
                    ok = False
                    self.errors.append(repr(e))
            if ok and plan["check"][k]:
                self.sampled.append((c, k, np.array(scores), np.array(ids)))
            rec.append((t, time.perf_counter(), ok))
            time.sleep(plan["think_s"][k])
            j += 1

    def run_window(self, seconds: float, window_span: str) -> None:
        self.records = [[] for _ in range(self.mix["makers"])]
        self.sampled, self.errors = [], []
        m0 = dict(self.server.metrics)
        s0 = dict(self.server.engine.search_stats)
        self.t0, self.t1 = kbserve.run_clients(self.mix["makers"],
                                               self._maker, seconds,
                                               window_span)
        self.counters = {k: self.server.metrics[k] - m0[k]
                         for k in ("requests", "dispatches")}
        self.counters.update({f"search_{k}": v - s0[k] for k, v in
                              self.server.engine.search_stats.items()})

    def finish(self) -> None:
        pass

    def free(self) -> None:
        kbserve.unobserve(self.server.engine, ops=("nn_search",))
        kbserve.free_server(self.server)
        del self.server

    def stats(self) -> dict:
        recs = [r for rs in self.records for r in rs]
        done = [r for r in recs if r[2] and r[1] <= self.t1]
        s = self.cfg["server"]
        return {
            "window_s": self.t1 - self.t0,
            "latencies_s": np.asarray([r[1] - r[0] for r in done]),
            "attempted": len(recs),
            "failed": sum(1 for r in recs if not r[2]),
            "counters": self.counters,
            "calls": [e for e in self.log.calls
                      if self.t0 <= e["t"] <= self.t1],
            "centroids": self.centroids,
            "nprobe": s["ann_nprobe"],
            "rows": self.cfg["rows"],
            "dim": self.dim,
            "k": self.mix["k"],
        }

    def _run_shape(self) -> dict:
        """The window's engine calls by requests in the call."""
        q, h = self.mix["queries_per_request"], {}
        for e in self.log.calls:
            if self.t0 <= e["t"] <= self.t1:
                size = e["payload"].shape[0] // q
                h[size] = h.get(size, 0) + 1
        return {"nn_search": {str(k): v for k, v in sorted(h.items())}}

    def check(self) -> dict:
        """Exact search of the seed's bank for the sampled queries. Runs
        after ``free``."""
        import jax
        import jax.numpy as jnp
        k, m = self.mix["k"], self.cfg["mixture"]
        q = np.concatenate([self.plans[c]["queries"][j]
                            for c, j, _, _ in self.sampled]) \
            if self.sampled else np.zeros((0, self.dim), np.float32)
        got_s = np.concatenate([s for _, _, s, _ in self.sampled]) \
            if self.sampled else np.zeros((0, k))
        got_i = np.concatenate([i for _, _, _, i in self.sampled]) \
            if self.sampled else np.zeros((0, k), np.int64)
        table = bank.clustered_bank(self.cfg["rows"], self.dim, m["centers"],
                                    m["noise"], self.seed)
        search = jax.jit(lambda t, qb: jax.lax.top_k(jnp.matmul(
            qb, t.T, precision=jax.lax.Precision.HIGHEST), k))
        ref_i = np.zeros((q.shape[0], k), np.int64)
        block = self.mix["reference_block"]
        for lo in range(0, q.shape[0], block):
            qb = q[lo:lo + block]
            pad = block - qb.shape[0]
            qb = np.concatenate([qb, np.zeros((pad, self.dim), np.float32)])
            ref_i[lo:lo + block - pad] = np.asarray(
                search(table, jnp.asarray(qb))[1])[:block - pad]
        n = self.cfg["rows"]
        valid = (got_i >= 0) & (got_i < n)
        bad = int(np.sum(~valid.all(axis=1)))
        safe = np.where(valid, got_i, 0)
        bad += int(sum(len(set(r)) < k for r in got_i))
        bad += int(np.sum(np.any(np.diff(got_s, axis=1) > 0, axis=1)))
        rows = np.asarray(table[jnp.asarray(safe.reshape(-1))], np.float64
                          ).reshape(*safe.shape, self.dim)
        true = np.einsum("qd,qkd->qk", q.astype(np.float64), rows)
        norm = (np.linalg.norm(q, axis=1)[:, None] *
                np.linalg.norm(rows, axis=2))
        gap = np.where(valid, np.abs(got_s - true) / np.maximum(norm, 1e-30),
                       np.inf)
        score_err = float(gap.max()) if gap.size else 0.0
        recall = (float(np.mean([len(set(a) & set(b)) / k
                                 for a, b in zip(got_i, ref_i)]))
                  if q.shape[0] else 0.0)
        if self.with_control:
            ref_rows = np.asarray(table[jnp.asarray(ref_i.reshape(-1))],
                                  np.float64).reshape(*ref_i.shape, self.dim)
            ctl = np.zeros(ref_i.shape)
            for i in range(q.shape[0]):
                ctl[i] = reference.high_scores(q[i:i + 1], ref_rows[i])[0]
            true_r = np.einsum("qd,qkd->qk", q.astype(np.float64), ref_rows)
            rnorm = (np.linalg.norm(q, axis=1)[:, None] *
                     np.linalg.norm(ref_rows, axis=2))
            self.control = {"score_err": float(np.max(
                np.abs(ctl - true_r) / np.maximum(rnorm, 1e-30)))}
        del table
        self.info = {"sampled_queries": int(q.shape[0]),
                     "errors": self.errors[:3], "runs": self._run_shape()}
        lim = self.cfg["limits"]
        return {
            "recall_at_k": (recall, ">=", lim["recall_at_k"]),
            "score_err": (score_err, "<=", lim["score_err"]),
            "malformed_replies": (bad, "<=", 0),
        }
