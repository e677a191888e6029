"""Multi-process CI smoke: the cross-process CARLS topology end to end.

Boots the real deployment shapes with zero test scaffolding:

``--phase single`` (ISSUE 5 — one bank, one worker):
1. ``repro.launch.serve --kb --listen 127.0.0.1:0`` in one process
   (ephemeral port parsed from its "listening on" line),
2. ``repro.launch.maker_worker --connect`` in a second process running a
   checkpoint-free ``graph_builder`` fleet for a few steps,
3. asserts the worker reported ``rows_written > 0`` and exited 0,
4. SIGTERMs the server and asserts it printed its serving summary with a
   non-zero wire-request count, and exited 0.

``--phase router`` (ISSUE 6 — the partitioned fleet):
1. TWO ``serve --kb --kb-join i/2 --listen 127.0.0.1:0`` processes, each
   hosting its consistent-hash slice of one 256-row bank,
2. a ``connect_kb("host:p0,host:p1")`` client process that updates rows it
   KNOWS live on different partitions, reads them back, and runs an
   nn_search whose result set must span both partitions,
3. ``maker_worker --connect host:p0,host:p1`` — the unchanged worker
   routed transparently through a ``KBRouter`` — with rows_written > 0,
4. SIGTERMs both members and asserts EACH served wire requests > 0 (both
   partitions took traffic, none sat idle behind the router).

``--phase mixed`` (ISSUE 10 — the v4 multiplexed wire):
1. one ``serve --kb --listen`` bank process,
2. a client process sharing ONE pipelined connection between nn_search
   hog threads and a point-lookup thread (the workload FIFO response
   matching head-of-line-blocked before v4),
3. asserts zero client errors and a generous absolute lookup-p99 bound —
   a v3-style delivery stall parks lookups behind every in-flight bulk
   search and blows the bound; bit-identity is the bench's job
   (``kb_serving/mixed/*``), this phase proves the real-process path.

``--phase failover`` (ISSUE 8 — the self-healing fleet):
1. TWO partition members plus ONE standby (``serve --kb-join 0/2
   --replica-of host:p0``) that boot-copies its primary's rows,
2. a ``maker_worker --connect host:p0|host:s0,host:p1`` fleet; the moment
   it reports connected, member p0 is SIGKILLed — so essentially every
   maker step runs against the killed fleet,
3. asserts the worker still finished ALL its steps with zero errors and
   rows_written > 0: its router promoted the standby mid-run,
4. SIGTERMs the survivor and the promoted standby and asserts each served
   wire traffic.

Chips: each ``serve`` process gets a chip of its own on a TPU host and a
phase needing more servers than the host has chips fails at boot
(``repro.launch.fleet.ChipPlan``); workers and clients run on the CPU.
``JAX_PLATFORMS=cpu`` runs every process on the CPU.

Usage:
  python tools/smoke_multiproc.py [--phase single|router|mixed|failover|all]
(exit 0 = pass)
"""
from __future__ import annotations

import argparse
import os
import re
import select
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
from repro.launch.fleet import ChipPlan, host_chips  # noqa: E402  (no jax)

STARTUP_TIMEOUT_S = 300         # cold jax import + jit warmup on CI

# runs inside a client subprocess (needs the repro jax stack, which the
# driver itself never imports): prove the router splits writes/reads
# across both fleet members and merges nn results across them
_ROUTER_CLIENT = r"""
import sys
import numpy as np
from repro.core import connect_kb
from repro.core.kb_router import PartitionMap

kb = connect_kb(sys.argv[1], client_name="smoke-router")
pmap = PartitionMap(kb.num_entries, 2)
ids = np.array([int(pmap.global_ids(0)[0]), int(pmap.global_ids(1)[0])])
vals = np.eye(2, kb.dim, dtype=np.float32) * 100.0
kb.update(ids, vals)                      # one row on EACH partition
back = kb.lookup(ids)
assert np.allclose(back, vals), "cross-partition lookup mismatch"
scores, nn = kb.nn_search(vals, k=1)      # each planted row dominates its
owners = set(int(o) for o in pmap.owner_of(nn[:, 0]))   # own query
assert nn[0, 0] == ids[0] and nn[1, 0] == ids[1], (nn, ids)
assert owners == {0, 1}, f"nn results stayed on partitions {owners}"
kb.close()
print("router-client OK")
"""


# mixed workload over one connection: bulk nn_search hogs + a timed point
# lookup thread. argv: spec, p99 bound in ms. Prints the measured p99 and
# an error count that must be zero.
_MIXED_CLIENT = r"""
import sys, threading, time
import numpy as np
from repro.core import connect_kb

spec, bound_ms = sys.argv[1], float(sys.argv[2])
kb = connect_kb(spec, client_name="smoke-mixed")
n = kb.num_entries
table = np.random.default_rng(0).normal(size=(64, kb.dim)) \
    .astype(np.float32)
kb.lookup(np.arange(16)); kb.nn_search(table[:16], 4)      # warm the wire
errors, lat = [], []
done = threading.Event()

def hog(h):
    rng = np.random.default_rng(40 + h)
    while not done.is_set():
        try:
            kb.nn_search(table[rng.integers(0, 64, (32,))], 8)
        except Exception as e:
            errors.append(e)
            return

def looker():
    rng = np.random.default_rng(99)
    try:
        for _ in range(80):
            ids = rng.integers(0, n, (16,))
            t0 = time.perf_counter()
            kb.lookup(ids)
            lat.append(time.perf_counter() - t0)
    except Exception as e:
        errors.append(e)
    finally:
        done.set()

hogs = [threading.Thread(target=hog, args=(h,)) for h in range(3)]
for t in hogs: t.start()
time.sleep(0.05)
lt = threading.Thread(target=looker)
lt.start(); lt.join()
for t in hogs: t.join()
st = kb.stats()["transport"]
kb.close()
p99 = float(np.percentile(np.asarray(lat), 99) * 1e3)
assert not errors, f"client errors: {errors[:3]}"
assert p99 <= bound_ms, f"lookup p99 {p99:.1f}ms over {bound_ms}ms bound"
print(f"mixed-client OK p99={p99:.2f}ms errors=0 "
      f"reissued={st['reissued']}")
"""


def _env():
    """Client-only children (workers, client scripts): pinned to the CPU."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return ChipPlan.client_env(env)


def _boot_server(chips, extra_args):
    """Start a serve.py bank process and return (proc, port) once its
    "listening on" line appears — select-with-deadline, NOT a bare
    readline: a server that wedges before printing anything must fail at
    the startup budget, not at the CI job timeout with zero diagnostics."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--kb",
         "--kb-entries", "256", "--kb-dim", "32",
         "--listen", "127.0.0.1:0", "--serve-seconds", "600", *extra_args],
        env=chips.serving_env(_env()), cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    lines = []
    deadline = time.time() + STARTUP_TIMEOUT_S
    while True:
        if time.time() > deadline:
            raise RuntimeError("server never reported listening within "
                               f"{STARTUP_TIMEOUT_S}s:\n" + "".join(lines))
        ready, _, _ = select.select([proc.stdout], [], [], 5.0)
        if not ready:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server exited early:\n{''.join(lines)}")
            continue
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"server exited early:\n{''.join(lines)}")
        lines.append(line)
        print("[serve]", line, end="", flush=True)
        m = re.search(r"listening on [\d.]+:(\d+)", line)
        if m:
            return proc, int(m.group(1))


def _stop_server(proc, name):
    """SIGTERM, collect the summary, assert a clean exit that actually
    served wire traffic."""
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    print(f"[{name}]", out, flush=True)
    if proc.returncode != 0:
        raise RuntimeError(f"{name} exited {proc.returncode}")
    m = re.search(r"(\d+) wire requests", out)
    if not m or int(m.group(1)) <= 0:
        raise RuntimeError(f"{name} served no wire requests")


def _run_worker(connect_spec):
    worker = subprocess.run(
        [sys.executable, "-m", "repro.launch.maker_worker",
         "--connect", connect_spec,
         "--makers", "graph_builder", "--steps", "5", "--batch", "16"],
        env=_env(), cwd=ROOT, capture_output=True, text=True,
        timeout=STARTUP_TIMEOUT_S)
    print("[worker]", worker.stdout, worker.stderr, flush=True)
    if worker.returncode != 0:
        raise RuntimeError(f"worker exited {worker.returncode}")
    m = re.search(r"rows_written=(\d+)", worker.stdout)
    if not m or int(m.group(1)) <= 0:
        raise RuntimeError("worker reported no rows_written")


def _chips(n_servers):
    """A fresh chip plan for one phase, refused at boot if the phase's
    servers cannot each own a chip."""
    plan = ChipPlan(host_chips())
    plan.require(n_servers)
    return plan


def phase_single() -> None:
    serve, port = _boot_server(_chips(1), [])
    try:
        _run_worker(f"127.0.0.1:{port}")
        _stop_server(serve, "serve")
    finally:
        if serve.poll() is None:
            serve.kill()
    print("single-server smoke: OK", flush=True)


def phase_router() -> None:
    members = []
    chips = _chips(2)
    try:
        for i in range(2):
            members.append(_boot_server(chips, ["--kb-join", f"{i}/2"]))
        spec = ",".join(f"127.0.0.1:{port}" for _, port in members)

        client = subprocess.run(
            [sys.executable, "-c", _ROUTER_CLIENT, spec],
            env=_env(), cwd=ROOT, capture_output=True, text=True,
            timeout=STARTUP_TIMEOUT_S)
        print("[client]", client.stdout, client.stderr, flush=True)
        if client.returncode != 0 or "router-client OK" not in client.stdout:
            raise RuntimeError(f"router client failed ({client.returncode})")

        _run_worker(spec)
        for i, (proc, _) in enumerate(members):
            _stop_server(proc, f"serve-p{i}")
    finally:
        for proc, _ in members:
            if proc.poll() is None:
                proc.kill()
    print("router smoke: OK", flush=True)


def phase_mixed() -> None:
    serve, port = _boot_server(_chips(1), [])
    try:
        client = subprocess.run(
            [sys.executable, "-c", _MIXED_CLIENT,
             f"127.0.0.1:{port}", "2000"],
            env=_env(), cwd=ROOT, capture_output=True, text=True,
            timeout=STARTUP_TIMEOUT_S)
        print("[client]", client.stdout, client.stderr, flush=True)
        if client.returncode != 0 or "mixed-client OK" not in client.stdout:
            raise RuntimeError(f"mixed client failed ({client.returncode})")
        _stop_server(serve, "serve")
    finally:
        if serve.poll() is None:
            serve.kill()
    print("mixed smoke: OK", flush=True)


def phase_failover() -> None:
    procs = []
    worker = None
    chips = _chips(3)
    try:
        p0 = _boot_server(chips, ["--kb-join", "0/2"])
        procs.append(p0)
        p1 = _boot_server(chips, ["--kb-join", "1/2"])
        procs.append(p1)
        s0 = _boot_server(chips, ["--kb-join", "0/2",
                                  "--replica-of", f"127.0.0.1:{p0[1]}"])
        procs.append(s0)
        spec = (f"127.0.0.1:{p0[1]}|127.0.0.1:{s0[1]},"
                f"127.0.0.1:{p1[1]}")
        worker = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.maker_worker",
             "--connect", spec, "--makers", "graph_builder",
             "--steps", "20", "--batch", "16",
             "--seconds", str(STARTUP_TIMEOUT_S),
             "--max-retries", "1", "--reconnect-backoff", "0.01"],
            env=_env(), cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        # kill the primary the moment the worker is connected (before its
        # makers start): every step must then ride the promoted standby
        lines = []
        deadline = time.time() + STARTUP_TIMEOUT_S
        while True:
            if time.time() > deadline:
                raise RuntimeError("worker never connected:\n"
                                   + "".join(lines))
            line = worker.stdout.readline()
            if not line:
                raise RuntimeError("worker exited before connecting:\n"
                                   + "".join(lines))
            lines.append(line)
            print("[worker]", line, end="", flush=True)
            if "maker-worker connected" in line:
                break
        p0[0].send_signal(signal.SIGKILL)
        p0[0].wait(timeout=60)
        print("[driver] SIGKILLed member p0; worker must promote s0",
              flush=True)
        out, _ = worker.communicate(timeout=STARTUP_TIMEOUT_S)
        print("[worker]", out, flush=True)
        if worker.returncode != 0:
            raise RuntimeError(f"worker exited {worker.returncode} after "
                               "the primary was killed")
        m = re.search(r"done: steps=(\d+) rows_written=(\d+) errors=(\d+)",
                      out)
        if not m:
            raise RuntimeError("worker printed no final report")
        steps, rows, errors = (int(g) for g in m.groups())
        if steps < 20 or rows <= 0 or errors > 0:
            raise RuntimeError(
                f"maker did not keep advancing through fail-over: "
                f"steps={steps} rows_written={rows} errors={errors}")
        _stop_server(p1[0], "serve-p1")
        _stop_server(s0[0], "serve-s0")     # the PROMOTED member
    finally:
        if worker is not None and worker.poll() is None:
            worker.kill()
        for proc, _ in procs:
            if proc.poll() is None:
                proc.kill()
    print("failover smoke: OK", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase",
                    choices=["single", "router", "mixed", "failover",
                             "all"],
                    default="all")
    args = ap.parse_args()
    if args.phase in ("single", "all"):
        phase_single()
    if args.phase in ("router", "all"):
        phase_router()
    if args.phase in ("mixed", "all"):
        phase_mixed()
    if args.phase in ("failover", "all"):
        phase_failover()
    print("multi-process smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
