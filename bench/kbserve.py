"""The Knowledge Bank server as the KB cells run it: built from the
configuration file, filled with the seed's clustered bank, and its engine
observed through instance attributes.

The observation wraps the engine's ``lookup`` / ``lazy_grad`` /
``nn_search`` in a host span (``engine.<op>``) and logs each call's
arguments in dispatch order. The server's dispatcher is one thread, so
the log is the order in which the bank saw the work, and the device work
inside an ``engine.*`` span belongs to that call (or to an earlier
write whose device work the call waits on).
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

import bank


class EngineLog:
    """Calls of one engine, in the order the dispatcher made them. Each
    entry: ``op``, ``t`` (host clock at the call); ``ids`` (int64 copy)
    for point ops; ``payload`` (gradients or queries, as passed)."""

    def __init__(self):
        self.calls = []
        self.lock = threading.Lock()

    def __len__(self):
        return len(self.calls)


def build_server(cfg: dict):
    """A ``KnowledgeBankServer`` with the configuration's settings, every
    other setting at its default."""
    from repro.core import KnowledgeBankServer
    s = cfg["server"]
    return KnowledgeBankServer(
        cfg["rows"], cfg["dim"], backend=s["backend"],
        lazy_lr=s["lazy_lr"], zmax=s["zmax"], search_mode=s["search_mode"],
        ann_nlist=s["ann_nlist"], ann_nprobe=s["ann_nprobe"])


def fill(server, cfg: dict, seed: int) -> None:
    """Load the seed's bank through the server's write path."""
    m = cfg["mixture"]
    table = bank.clustered_bank(cfg["rows"], cfg["dim"], m["centers"],
                                m["noise"], seed)
    server.update(np.arange(cfg["rows"]), np.asarray(table))


def observe(engine, log: EngineLog, ops=("lookup", "lazy_grad",
                                         "nn_search"), tamper=None):
    """Wrap the engine's ops, as instance attributes, with spans and the
    call log. ``tamper`` (tests only) maps an op name and the original
    bound method to a replacement that breaks it: under the log, or,
    where ``tamper.where`` is ``"dispatcher"``, between the dispatcher
    and the log, as a fault of the dispatcher would."""
    where = getattr(tamper, "where", "engine")
    for op in ops:
        orig = getattr(engine, op)
        if tamper is not None and where == "engine":
            orig = tamper(op, orig)

        def call(*args, _op=op, _orig=orig, **kw):
            entry = {"op": _op, "t": time.perf_counter()}
            if _op == "nn_search":
                entry["payload"] = np.asarray(args[0])
            else:
                entry["ids"] = np.asarray(args[0]).reshape(-1).astype(
                    np.int64)
                if _op == "lazy_grad":
                    entry["payload"] = np.asarray(args[1])
            with log.lock:
                log.calls.append(entry)
            with jax.profiler.TraceAnnotation(f"engine.{_op}"):
                return _orig(*args, **kw)

        setattr(engine, op, call if tamper is None or where == "engine"
                else tamper(op, call))


def unobserve(engine, ops=("lookup", "lazy_grad", "nn_search")) -> None:
    for op in ops:
        engine.__dict__.pop(op, None)


def run_clients(n: int, body, seconds: float, window_span: str):
    """Start ``n`` threads running ``body(client, stop_at)`` together and
    wait for all. The host span ``window_span`` covers exactly the
    measured window. Returns (t0, t1): the window on the host clock."""
    gate = threading.Barrier(n + 1)
    errors = []
    window = {}

    def main(c):
        gate.wait()
        try:
            body(c, window["t1"])
        except BaseException as e:          # reported, then re-raised
            errors.append(e)

    threads = [threading.Thread(target=main, args=(c,), daemon=True,
                                name=f"bench-client-{c}") for c in range(n)]
    for t in threads:
        t.start()
    with jax.profiler.TraceAnnotation(window_span):
        window["t0"] = time.perf_counter()
        window["t1"] = window["t0"] + seconds
        gate.wait()
        time.sleep(max(0.0, window["t1"] - time.perf_counter()))
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return window["t0"], window["t1"]


def free_server(server) -> None:
    """Close the server and drop the device state it holds."""
    server.close()
    server.engine.state = None
    server.engine.ann_index = None
