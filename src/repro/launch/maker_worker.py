"""Standalone knowledge-maker worker: any registered maker kind as its own
OS process against a remote Knowledge Bank.

  # terminal 1: host the bank
  PYTHONPATH=src python -m repro.launch.serve --kb --listen 127.0.0.1:7787

  # terminal 2..N: crash-isolated maker fleet, one process each
  PYTHONPATH=src python -m repro.launch.maker_worker \
      --connect 127.0.0.1:7787 --makers graph_builder --steps 50

This is the paper's deployment shape for knowledge makers (§2: independent
jobs "across hardware platforms" sharing the bank): the worker dials the
bank over the TCP transport (``repro.core.kb_transport``), polls its OWN
checkpoint directory (``--ckpt-dir``, the cross-process weight channel —
required for every maker kind except ``graph_builder``), paces itself, and
crashes alone — the bank and its other clients never notice. The maker
code itself is the unchanged ``MakerRuntime``/``MakerJob`` fleet: the only
difference from an in-process run is which ``KBClient`` it holds, so a
maker's bank writes are bit-identical in-process vs worker-process for the
same checkpoint and seed (tests/test_kb_transport.py proves it).

Exit status: 0 after a clean run, 2 when the fleet produced no steps and
only errors (so supervisors and the CI smoke can tell a dead worker from a
quiet one).
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time

import jax
import numpy as np

from repro.checkpoint import DiskCheckpointStore
from repro.configs import ARCH_IDS, get_config
from repro.core import (MakerRuntime, connect_kb, format_maker_stats,
                        make_embed_fn)
from repro.data import SyntheticGraphCorpus
from repro.env import enable_compile_cache
from repro.models import build_model
from repro.sharding.partition import DistContext


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--connect", required=True,
                    metavar="HOST:PORT[,HOST:PORT,...]",
                    help="knowledge-bank transport endpoint (serve.py "
                         "--listen); a comma list names a partitioned "
                         "fleet in ring order (serve.py --kb-join), "
                         "routed through a KBRouter transparently")
    ap.add_argument("--makers", default="graph_builder",
                    help="comma list of maker kinds to run in this process "
                         "(embedding_refresh,label_mining,graph_agreement,"
                         "graph_builder)")
    ap.add_argument("--arch", choices=ARCH_IDS, default="yi-6b",
                    help="model arch for checkpoint-loading makers")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--nodes", type=int, default=0,
                    help="corpus nodes; 0 = the bank's num_entries "
                         "(from the wire handshake)")
    ap.add_argument("--clusters", type=int, default=4)
    ap.add_argument("--labeled-frac", type=float, default=0.3)
    ap.add_argument("--label-noise", type=float, default=0.3)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--period", type=float, default=0.0,
                    help="per-maker pacing floor in seconds")
    ap.add_argument("--node-slice", default="", metavar="I/N",
                    help="be worker I of an N-worker pack: makers in this "
                         "process touch only slice I of the node space. "
                         "Against a partitioned fleet whose member count "
                         "divides N evenly, slices follow the ring "
                         "(KBRouter.partition_slices), so every maker "
                         "batch stays on one partition — the router's "
                         "no-copy fast path; otherwise a round-robin "
                         "1-in-N slice")
    ap.add_argument("--steps", type=int, default=0,
                    help="stop after this many total maker steps "
                         "(0 = run until SIGINT/SIGTERM)")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="wall-clock cap (0 = none)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory to poll (the cross-process "
                         "weight channel; required for ckpt-loading makers)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--client-name", default="",
                    help="free-form label sent in the wire handshake")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="transport redials per request (at-least-once)")
    ap.add_argument("--reconnect-backoff", type=float, default=0.05,
                    help="exponential-backoff base (s) between redials "
                         "(capped + jittered; see docs/tuning.md)")
    ap.add_argument("--sock-buf", type=int, default=0,
                    help="SO_SNDBUF/SO_RCVBUF bytes (0 = OS default)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # a worker is a client of the bank: launchers pin it to the CPU so the
    # chips stay with the serving processes
    print(f"maker-worker: client-only process, jax platform "
          f"{jax.default_backend()} (JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS') or 'unset'})", flush=True)

    kinds = [k.strip() for k in args.makers.split(",") if k.strip()]
    client = connect_kb(
        args.connect,
        client_name=args.client_name or f"maker-worker:{','.join(kinds)}",
        max_retries=args.max_retries,
        reconnect_backoff_s=args.reconnect_backoff, sock_buf=args.sock_buf)
    n = args.nodes or client.num_entries
    if n > client.num_entries:
        # out-of-range ids would be silently dropped by the device scatter
        # — the worker would report rows_written > 0 while most knowledge
        # never lands (run_async_training enforces the same invariant)
        ap.error(f"--nodes {n} exceeds the bank's "
                 f"{client.num_entries} entries")
    print(f"maker-worker connected to {args.connect} "
          f"(bank: {client.num_entries} x {client.dim}, corpus nodes: {n})",
          flush=True)

    needs_ckpt = any(k != "graph_builder" for k in kinds)
    corpus = ckpts = embed = None
    if needs_ckpt:
        if not args.ckpt_dir:
            ap.error(f"makers {kinds} load checkpoints: pass --ckpt-dir")
        cfg = get_config(args.arch).reduced()
        if args.layers:
            cfg = cfg.replace(num_layers=args.layers)
        if cfg.d_model != client.dim:
            ap.error(f"model d_model {cfg.d_model} != bank dim {client.dim}")
        model = build_model(cfg)
        dist = DistContext()
        # the init params are shape/dtype TEMPLATE only — every loaded
        # checkpoint replaces the values
        ckpts = DiskCheckpointStore(
            args.ckpt_dir, template=model.init(jax.random.key(args.seed)))
        embed = jax.jit(make_embed_fn(model, dist))
        corpus = SyntheticGraphCorpus(
            num_nodes=n, vocab_size=cfg.vocab_size, seq_len=args.seq + 1,
            neighbors_per_node=cfg.carls.num_neighbors,
            num_clusters=args.clusters, labeled_frac=args.labeled_frac,
            label_noise=args.label_noise, seed=args.seed)

    node_slice = None
    if args.node_slice:
        try:
            w_idx, w_total = (int(x) for x in args.node_slice.split("/"))
        except ValueError:
            ap.error(f"--node-slice wants I/N, got {args.node_slice!r}")
        if not (0 <= w_idx < w_total):
            ap.error(f"--node-slice {args.node_slice}: index out of range")
        slices = getattr(client, "partition_slices", None)
        parts = slices() if slices is not None else []
        if parts and w_total % len(parts) == 0:
            # ring-aligned pack: worker I mirrors partition I%P, taking
            # its 1-in-(N/P) round-robin share of that partition's ids —
            # every batch lands on one member (router fast path)
            mine = parts[w_idx % len(parts)]
            node_slice = mine[w_idx // len(parts)::w_total // len(parts)]
        else:
            node_slice = np.arange(n)[w_idx::w_total]
        node_slice = node_slice[node_slice < n]
        print(f"maker-worker node-slice {args.node_slice}: "
              f"{node_slice.size} of {n} nodes"
              f"{' (ring-aligned)' if parts else ''}", flush=True)

    rt = MakerRuntime(client, corpus,
                      num_entries=None if corpus is not None else n,
                      ckpts=ckpts, embed_fn=embed)
    for kind in kinds:
        rt.register(kind, batch_size=args.batch, min_period_s=args.period,
                    node_slice=node_slice)

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    deadline = time.time() + args.seconds if args.seconds else None
    rt.start()
    while not stop.is_set():
        if args.steps and sum(j.steps for j in rt.jobs) >= args.steps:
            break
        if deadline is not None and time.time() > deadline:
            break
        stop.wait(0.05)
    rt.stop()

    for line in format_maker_stats(rt.stats()):
        print(line)
    steps = sum(j.steps for j in rt.jobs)
    rows = sum(j.rows_written for j in rt.jobs)
    errors = sum(j.errors for j in rt.jobs)
    try:
        tstats = client.stats().get("transport", {})
    except Exception:       # bank already gone: the counters are client-
        tstats = {}         # side but ride on a stats() round-trip
    extra = (f" reconnects={tstats.get('reconnects', 0)}"
             f" reissued={tstats.get('reissued', 0)}" if tstats else "")
    print(f"maker-worker done: steps={steps} rows_written={rows} "
          f"errors={errors}{extra}", flush=True)
    client.close()
    return 2 if (steps == 0 and errors > 0) else 0


if __name__ == "__main__":
    sys.exit(main())
