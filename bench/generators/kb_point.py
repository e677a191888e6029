"""Closed-loop point traffic on the Knowledge Bank server: each client
thread calls ``lookup`` or ``lazy_grad`` with a fixed number of ids, waits
for the reply, and calls again (YCSB core workloads, Cooper et al., SoCC
2010, with "read" as ``lookup`` and "update" as ``lazy_grad``).

Every seed gives each client the same number of requests of each kind;
the seed decides their order, their ids and their gradients.

``correct``: the float64 reference replays the engine's calls in the
order the dispatcher made them, from the seed's bank. A sample of the
served ``lookup`` replies, drawn from the seed before the window, is
compared with the reference at the dispatch that served it; after the
window the rows that ``lazy_grad`` calls touched (or a sample of them
drawn from the seed) are read back through the server and compared too.
The replay follows the log, so the log itself is held to the clients:
every (id, gradient row) pair of an acknowledged ``lazy_grad`` has to
reach the engine exactly once, and nothing else may, across the
dispatcher's coalescing.
"""
from __future__ import annotations

import time

import numpy as np

import bank
import kbserve
import reference

_SCHEDULE = 4096        # requests per client before its schedule repeats


class Generator:
    def __init__(self, cfg: dict, mix: dict, seed: int, tamper=None,
                 control: bool = False):
        self.cfg, self.mix, self.seed, self.tamper = cfg, mix, seed, tamper
        self.with_control = control
        self.dim = cfg["dim"]

    # -- set-up --------------------------------------------------------------

    def _schedules(self):
        mix, n = self.mix, self.mix["ids_per_request"]
        keys = bank.ScrambledZipf(self.cfg["rows"], mix["zipf_theta"])
        n_write = int(round(_SCHEDULE * mix["lazy_grad_share"]))
        n_check = int(round(_SCHEDULE * mix["check_share"]))
        self.plans = []
        for c in range(mix["clients"]):
            rng = np.random.default_rng([self.seed, 1, c])
            is_write = np.zeros(_SCHEDULE, bool)
            is_write[:n_write] = True
            rng.shuffle(is_write)
            check = np.zeros(_SCHEDULE, bool)
            check[rng.choice(np.flatnonzero(~is_write), n_check,
                             replace=False)] = True
            ids = keys.sample(rng, (_SCHEDULE, n))
            self.plans.append({"write": is_write, "check": check, "ids": ids,
                               "grad_seed": [self.seed, 2, c]})

    def setup(self) -> None:
        self._schedules()
        self.server = kbserve.build_server(self.cfg)
        kbserve.fill(self.server, self.cfg, self.seed)
        top = self.mix["clients"] * self.mix["ids_per_request"]
        self.server.warmup(top)
        eng = self.server.engine
        self.log = kbserve.EngineLog()
        kbserve.observe(eng, self.log, ops=("lookup", "lazy_grad"),
                        tamper=self.tamper)
        # every reply length a coalesced run can have: the engine slices
        # its padded result to the run's length. No row holds a cached
        # gradient yet, so these lookups leave the bank as it is.
        n = self.mix["ids_per_request"]
        for m in range(1, self.mix["clients"] + 1):
            self.server.lookup(np.arange(m * n) % self.cfg["rows"])

    # -- the measured window -------------------------------------------------

    def _client(self, c: int, stop_at: float):
        import jax
        plan, n = self.plans[c], self.mix["ids_per_request"]
        rng = np.random.default_rng(plan["grad_seed"])
        rec = self.records[c]
        j = 0
        while True:
            t = time.perf_counter()
            if t >= stop_at:
                return
            k = j % _SCHEDULE
            ids = plan["ids"][k]
            before = len(self.log)
            ok = True
            if plan["write"][k]:
                g = rng.standard_normal((n, self.dim)).astype(np.float32)
                out = rng.random(n) < self.mix["grad_outlier_share"]
                g[out] *= self.mix["grad_outlier_scale"]
                with jax.profiler.TraceAnnotation("bench.lazy_grad"):
                    try:
                        self.server.lazy_grad(ids, g)
                    except Exception as e:      # counted as failed
                        ok = False
                        self.errors.append(repr(e))
                if ok:
                    self.acked.append((ids, g))
                op = "lazy_grad"
            else:
                with jax.profiler.TraceAnnotation("bench.lookup"):
                    try:
                        rows = self.server.lookup(ids)
                    except Exception as e:
                        ok, rows = False, None
                        self.errors.append(repr(e))
                op = "lookup"
                if ok and plan["check"][k]:
                    self.sampled.append((before, len(self.log), ids,
                                         np.array(rows)))
            rec.append((t, time.perf_counter(), n, ok, op))
            j += 1

    def run_window(self, seconds: float, window_span: str) -> None:
        self.records = [[] for _ in range(self.mix["clients"])]
        self.sampled, self.errors, self.acked = [], [], []
        m0 = dict(self.server.metrics)
        self.t0, self.t1 = kbserve.run_clients(self.mix["clients"],
                                               self._client, seconds,
                                               window_span)
        self.counters = {k: self.server.metrics[k] - m0[k]
                         for k in ("requests", "dispatches")}

    def finish(self) -> None:
        """After the window: read back the rows that ``lazy_grad`` calls
        touched (all of them, or a sample drawn from the seed where there
        are more than ``readback_max``), through the server, at reply
        lengths the window used."""
        written = [e["ids"] for e in self.log.calls
                   if e["op"] == "lazy_grad"]
        ids = (np.unique(np.concatenate(written)) if written
               else np.zeros(0, np.int64))
        cap = self.mix["readback_max"]
        if ids.size > cap:
            rng = np.random.default_rng([self.seed, 4])
            ids = np.sort(rng.choice(ids, cap, replace=False))
        self.readback_ids = ids
        step = self.mix["clients"] * self.mix["ids_per_request"]
        self.readback = [self.server.lookup(self.readback_ids[i:i + step])
                         for i in range(0, self.readback_ids.size, step)]

    def free(self) -> None:
        kbserve.unobserve(self.server.engine)
        kbserve.free_server(self.server)
        del self.server

    # -- what the metrics read -----------------------------------------------

    def stats(self) -> dict:
        recs = [r for rs in self.records for r in rs]
        done = [r for r in recs if r[3] and r[1] <= self.t1]
        return {
            "window_s": self.t1 - self.t0,
            "latencies_s": np.asarray([r[1] - r[0] for r in done]),
            "rows_done": int(sum(r[2] for r in done)),
            "attempted": len(recs),
            "failed": sum(1 for r in recs if not r[3]),
            "counters": self.counters,
            "calls": self._call_work(),
            "rows": self.cfg["rows"],
            "dim": self.dim,
        }

    def _call_work(self) -> list:
        """Per engine call in the window: ids, distinct rows, and rows
        holding cached gradients when it ran (tracked over the whole log:
        a ``lazy_grad`` makes its rows pending, a ``lookup`` clears them)."""
        pending = np.zeros(self.cfg["rows"], bool)
        out = []
        for e in self.log.calls:
            u = np.unique(e["ids"])
            n_pend = int(pending[u].sum())
            pending[u] = e["op"] == "lazy_grad"
            if self.t0 <= e["t"] <= self.t1:
                out.append({"op": e["op"], "n_ids": int(e["ids"].size),
                            "n_distinct": int(u.size), "n_pending": n_pend})
        return out

    # -- correct -------------------------------------------------------------

    def check(self) -> dict:
        """Replay the log on the reference; compare the sampled replies
        and the read-back rows. Runs after ``free``."""
        calls = self.log.calls
        universe = np.unique(np.concatenate(
            [e["ids"] for e in calls] + [self.readback_ids]))
        m = self.cfg["mixture"]
        table = bank.clustered_bank(self.cfg["rows"], self.dim, m["centers"],
                                    m["noise"], self.seed)
        rows0 = np.asarray(table[universe])
        del table
        ref = reference.BankReference(universe, rows0,
                                      lazy_lr=self.cfg["server"]["lazy_lr"],
                                      zmax=self.cfg["server"]["zmax"])
        want_at = {}
        for s, (lo, hi, ids, rows) in enumerate(self.sampled):
            for i in range(lo, hi):
                want_at.setdefault(i, []).append(s)
        served_err, unmatched = 0.0, set(range(len(self.sampled)))
        served_ctl = 0.0
        for i, e in enumerate(calls):
            if e["op"] == "lazy_grad":
                ref.lazy_grad(e["ids"], e["payload"])
                continue
            vals = ref.lookup(e["ids"])
            for s in want_at.get(i, ()):
                lo, hi, ids, rows = self.sampled[s]
                off = _find(e["ids"], ids)
                if off is None:
                    continue
                want = vals[off:off + ids.size]
                served_err = max(served_err, reference.row_error(rows, want))
                if self.with_control:
                    served_ctl = max(served_ctl, reference.row_error(
                        reference.high_gather(want), want))
                unmatched.discard(s)
        # the read-back lookups are the last calls in the log
        final = ref.table[np.searchsorted(ref.ids, self.readback_ids)]
        got = (np.concatenate(self.readback) if self.readback
               else np.zeros((0, self.dim)))
        rb_err = reference.row_error(got, final) if got.size else 0.0
        if self.with_control:
            rb_ctl = (reference.row_error(reference.high_gather(final),
                                          final) if got.size else 0.0)
            self.control = {"served_row_err": served_ctl,
                            "readback_row_err": rb_ctl}
        unpaired = _unpaired(
            self.acked, [(e["ids"], e["payload"]) for e in calls
                         if e["op"] == "lazy_grad"], self.dim)
        self.info = {"sampled_replies": len(self.sampled),
                     "readback_rows": int(self.readback_ids.size),
                     "replayed_calls": len(calls),
                     "acked_writes": len(self.acked),
                     "errors": self.errors[:3], **self._run_shape()}
        lim = self.cfg["limits"]
        return {
            "served_row_err": (served_err, "<=", lim["served_row_err"]),
            "readback_row_err": (rb_err, "<=", lim["readback_row_err"]),
            "served_unmatched": (len(unmatched), "<=", 0),
            "write_pairs_unmatched": (unpaired, "<=", 0),
        }

    def _run_shape(self) -> dict:
        """How the window's requests fell into dispatches: for each op, the
        number of engine calls by requests in the call, and each op's
        99th percentile latency in ms."""
        n = self.mix["ids_per_request"]
        runs = {}
        for e in self.log.calls:
            if self.t0 <= e["t"] <= self.t1:
                h = runs.setdefault(e["op"], {})
                size = str(e["ids"].size // n)
                h[size] = h.get(size, 0) + 1
        p99 = {}
        for op in ("lookup", "lazy_grad"):
            lat = [r[1] - r[0] for rs in self.records for r in rs
                   if r[4] == op and r[3] and r[1] <= self.t1]
            if lat:
                p99[op] = float(np.percentile(lat, 99)) * 1e3
        return {"runs": {op: dict(sorted(h.items(), key=lambda kv:
                                         int(kv[0])))
                         for op, h in runs.items()}, "p99_ms": p99}


_PAIR_MULT = np.random.default_rng(0x9E3779B97F4A7C15).integers(
    1, 2 ** 63, size=1 + 4096, dtype=np.uint64) | np.uint64(1)


def _pair_keys(ids, grads, dim: int) -> np.ndarray:
    """One 64-bit key per (id, gradient row) pair, from the id and every
    bit of the row (a random linear hash, modulo 2**64)."""
    ids = np.asarray(ids).reshape(-1)
    w = np.ascontiguousarray(grads, np.float32).reshape(ids.size, dim)
    w = w.view(np.uint32).astype(np.uint64)
    return ((w * _PAIR_MULT[1:dim + 1]).sum(axis=1, dtype=np.uint64)
            + ids.astype(np.uint64) * _PAIR_MULT[0])


def _unpaired(acked: list, logged: list, dim: int) -> int:
    """Pairs of the acknowledged writes and of the engine's logged writes
    that find no partner on the other side, counted as multisets."""
    keys = [_pair_keys(i, g, dim) for i, g in acked]
    n_acked = sum(k.size for k in keys)
    keys += [_pair_keys(i, g, dim) for i, g in logged]
    if not keys:
        return 0
    allk = np.concatenate(keys)
    _, inv = np.unique(allk, return_inverse=True)
    sign = np.ones(allk.size)
    sign[n_acked:] = -1.0
    return int(np.abs(np.bincount(inv, weights=sign)).sum())


def _find(haystack: np.ndarray, needle: np.ndarray):
    """Offset of ``needle`` as a contiguous run inside ``haystack``."""
    n = needle.size
    for off in np.flatnonzero(haystack[:haystack.size - n + 1] ==
                              needle[0]):
        if np.array_equal(haystack[off:off + n], needle):
            return int(off)
    return None
