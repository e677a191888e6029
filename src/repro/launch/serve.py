"""Serving launcher: batched LM prefill + decode, or the Knowledge-Bank
serving mode.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \
      --batch 4 --prompt-len 32 --gen 16

  PYTHONPATH=src python -m repro.launch.serve --kb --kb-backend pallas \
      --clients 8 --kb-search ivf --nlist 64 --nprobe 8

  PYTHONPATH=src python -m repro.launch.serve --kb --listen 127.0.0.1:7787

LM mode runs a reduced config end-to-end: prefill the prompt batch, then
greedy decode. Full-size serve programs (decode_32k / long_500k) are
exercised via the dry-run lowering of the same ``decode_step``.

KB mode stands up the request-coalescing KnowledgeBankServer on the chosen
engine backend (dense | pallas | sharded — the sharded bank's rows span a
(1, n) mesh over every local device, which the backend builds itself) and
drives it with concurrent lookup/lazy_grad/nn_search clients — the Figure-1
serving topology without the trainer attached. ``--kb-search ivf`` serves
nn_search from the asynchronously-clustered IVF index, rebuilt by a
background refresher thread (repro.core.ann_index); with ``--kb-backend
sharded`` each bank shard carries its own sub-index, queries merge
per-shard shortlists hierarchically, and stale shards re-cluster
independently. See docs/tuning.md for the knob guide.

``--listen HOST:PORT`` exposes the same bank on the TCP wire protocol
(repro.core.kb_transport) instead of driving synthetic local clients:
separate trainer/maker PROCESSES connect with ``launch/train.py
--kb-connect`` and ``launch/maker_worker.py --connect``, and their requests
coalesce with any in-process traffic. Port 0 binds an ephemeral port
(printed on the "listening" line). Serves until SIGINT/SIGTERM or
``--serve-seconds``, then prints the same serving summary.

Scale-out (repro.core.kb_router): ``--kb-partitions N`` splits the id
space over N in-process partition servers behind a ``KBRouter`` and drives
THAT with the synthetic clients — the one-process rehearsal of the
partitioned fleet. ``--kb-join I/N`` makes this process partition I of an
N-member fleet instead: it hosts ONLY the rows the consistent-hash ring
assigns to slot I (requires ``--listen``; ``--kb-entries`` is the GLOBAL
bank size, identical across the fleet), labels its handshake "I/N", and
refuses clients that pinned a different slot. Routers and workers connect
with a comma list in ring order: ``--kb-connect host:p0,host:p1``.
``--kb-reorder`` enables cross-op reordering in the dispatcher (commuting
requests hoist across the queue into bigger batched dispatches).
"""
from __future__ import annotations

import argparse
import signal
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.env import add_device_args, apply_device_args, enable_compile_cache
from repro.models import build_model
from repro.sharding.partition import DistContext


def format_kb_timing(m: dict) -> str:
    """Mean queue wait per request, and the dispatcher's and the engine's
    host time per device dispatch, the engine's placing of arguments on
    the device among it, and the dispatches whose donated bank state was
    not consumed, from a server's (or a fleet's summed)
    ``stats()["metrics"]``."""
    req = max(m.get("requests", 0), 1)
    disp = max(m.get("dispatches", 0), 1)
    wait = m.get("queue_wait_s", 0.0) / req
    dispatcher = (m.get("dispatcher_busy_s", 0.0)
                  - m.get("engine_call_s", 0.0)) / disp
    engine = (m.get("engine_op_s", 0.0) - m.get("engine_wait_s", 0.0)) / disp
    args = m.get("engine_args_s", 0.0) / disp
    return (f"kb host time: queue wait {wait * 1e3:.3f} ms/request, "
            f"dispatcher {dispatcher * 1e3:.3f} ms/dispatch, "
            f"engine {engine * 1e3:.3f} ms/dispatch "
            f"(args {args * 1e3:.3f}), "
            f"donation misses {m.get('donation_misses', 0)}")


def serve_kb_partitioned(args) -> None:
    """``--kb-partitions N``: the scale-out topology in one process — N
    partition servers behind a ``KBRouter``, synthetic clients driving the
    router. The cross-process version of the same fleet is N ``--kb-join``
    processes plus router-connected workers."""
    from repro.core import (InProcessTransport, KBRouter,
                            KnowledgeBankServer, PartitionMap)
    P = args.kb_partitions
    pmap = PartitionMap(args.kb_entries, P)
    servers = [KnowledgeBankServer(int(pmap.counts[p]), args.kb_dim,
                                   backend=args.kb_backend,
                                   coalesce=not args.no_coalesce,
                                   reorder=args.kb_reorder,
                                   search_mode=args.kb_search,
                                   ann_nlist=args.nlist,
                                   ann_nprobe=args.nprobe,
                                   storage=args.kb_storage,
                                   cache_rows=args.kb_cache_rows,
                                   resident_rows=args.kb_resident_rows,
                                   cold_after_rows=args.kb_cold_after,
                                   cold_dir=args.kb_cold_dir or None)
               for p in range(P)]
    router = KBRouter([InProcessTransport(s, partition=f"{p}/{P}")
                       for p, s in enumerate(servers)], pmap=pmap)
    rng = np.random.default_rng(args.seed)
    fill_vals = rng.normal(size=(args.kb_entries, args.kb_dim)) \
        .astype(np.float32)
    # tiered banks bound the distinct rows one write may touch — chunk the
    # initial fill to fit the resident tier
    chunk = (min(args.kb_resident_rows, args.kb_entries)
             if args.kb_resident_rows else args.kb_entries)
    for lo in range(0, args.kb_entries, chunk):
        router.update(np.arange(lo, min(lo + chunk, args.kb_entries)),
                      fill_vals[lo:lo + chunk])
    standbys = []
    if args.kb_replicas:
        # one warm standby per partition, filled through the router's
        # export/import stream and kept in sync by the write tee — the
        # in-process rehearsal of `serve.py --replica-of`; replicas
        # beyond the first queue as COLD spares the router fills and
        # attaches automatically when a promotion empties the slot
        for p in range(P):
            for i in range(args.kb_replicas):
                s = KnowledgeBankServer(int(pmap.counts[p]), args.kb_dim,
                                        backend=args.kb_backend,
                                        coalesce=not args.no_coalesce,
                                        reorder=args.kb_reorder,
                                        storage=args.kb_storage)
                standbys.append(s)
                if i == 0:
                    router.attach_standby(p, InProcessTransport(s),
                                          fill=True)
                else:
                    router.add_spare(p, InProcessTransport(s))
    for s in servers + standbys:
        s.warmup(args.batch * args.clients)
    router.nn_search(np.zeros((args.batch, args.kb_dim), np.float32), k=8)

    def client(t: int, n_calls: int):
        crng = np.random.default_rng(args.seed + 1 + t)
        for _ in range(n_calls):
            ids = crng.integers(0, args.kb_entries, (args.batch,))
            vals = router.lookup(ids)
            router.lazy_grad(ids, 0.01 * vals)
            router.nn_search(vals, k=8)

    threads = [threading.Thread(target=client, args=(t, args.gen))
               for t in range(args.clients)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    dt = time.perf_counter() - t0
    calls = args.clients * args.gen * 3
    stats = router.stats()
    router.close()
    for s in servers + standbys:
        s.close()
    m = stats["metrics"]
    print(f"kb-serve partitions={P} backend={args.kb_backend} "
          f"replicas={args.kb_replicas} "
          f"reorder={args.kb_reorder} clients={args.clients}: "
          f"{calls / dt:.0f} req/s ({dt / calls * 1e6:.0f} us/req), "
          f"coalescing x{stats['coalescing_factor']:.1f}, "
          f"{int(m.get('dispatches', 0))} device dispatches for "
          f"{int(m.get('requests', 0))} requests "
          f"({int(m.get('reorders', 0))} reordered), "
          f"router fast-path "
          f"{stats['router']['single_partition_fastpath']}"
          f"/{stats['router']['fanouts']} fan-outs", flush=True)
    print(format_kb_timing(m), flush=True)
    sst = stats.get("storage", {})
    if sst:
        print(f"  fleet storage mode={sst['mode']} "
              f"bytes/row={int(sst['bytes_per_row'])} "
              f"bytes_resident={int(sst['bytes_resident'])} "
              f"cache hits/misses={int(m.get('cache_hits', 0))}"
              f"/{int(m.get('cache_misses', 0))} "
              f"tier faults/spills={int(sst.get('tier_faults', 0))}"
              f"/{int(sst.get('tier_spills', 0))}")
    for p, s in enumerate(stats["partitions"]):
        sm = s["metrics"]
        print(f"  partition {p}/{P}: {int(pmap.counts[p])} rows, "
              f"{int(sm.get('requests', 0))} requests -> "
              f"{int(sm.get('dispatches', 0))} dispatches")


def serve_kb(args) -> dict:
    """Concurrent-client KB serving demo on the coalescing server. Returns
    the run's counters; raises if a client request or the index refresher
    failed."""
    from repro.core import (KnowledgeBankServer, MakerRuntime,
                            format_maker_stats)
    rng = np.random.default_rng(args.seed)
    partition_label = ""
    num_rows = args.kb_entries
    fill_ids = np.arange(args.kb_entries)
    if args.kb_join:
        # fleet-member mode: host ONLY slot I's rows of the GLOBAL bank.
        # Every member and every router computes the same ring from
        # (kb_entries, N), so sizing agrees without a config channel.
        from repro.core import PartitionMap
        try:
            idx, total = (int(x) for x in args.kb_join.split("/"))
        except ValueError:
            raise SystemExit(f"--kb-join wants I/N, got {args.kb_join!r}")
        if not (0 <= idx < total):
            raise SystemExit(f"--kb-join {args.kb_join}: index out of range")
        if not args.listen:
            raise SystemExit("--kb-join requires --listen (a fleet member "
                             "exists to serve remote routers)")
        pmap = PartitionMap(args.kb_entries, total)
        num_rows = int(pmap.counts[idx])
        partition_label = f"{idx}/{total}"
        # synthetic fill values keyed by GLOBAL id, so a partitioned
        # fleet's initial table matches a single server's row-for-row
        fill_ids = pmap.global_ids(idx)
    server = KnowledgeBankServer(num_rows, args.kb_dim,
                                 backend=args.kb_backend,
                                 coalesce=not args.no_coalesce,
                                 reorder=args.kb_reorder,
                                 search_mode=args.kb_search,
                                 ann_nlist=args.nlist,
                                 ann_nprobe=args.nprobe,
                                 storage=args.kb_storage,
                                 cache_rows=args.kb_cache_rows,
                                 resident_rows=args.kb_resident_rows,
                                 cold_after_rows=args.kb_cold_after,
                                 cold_dir=args.kb_cold_dir or None)
    if args.replica_of:
        # standby boot: instead of the synthetic fill, copy the primary's
        # full per-row state (every leaf, bit-identically) so this member
        # can be promoted in its place. The router re-fills on attach to
        # close the gap between this boot copy and the first teed write.
        if not args.kb_join:
            raise SystemExit("--replica-of requires --kb-join I/N (a "
                             "standby mirrors one ring slot)")
        from repro.core import SocketTransport, parse_hostport
        from repro.core.kb_protocol import (ExportRowsRequest,
                                            ImportRowsRequest)
        ph, pp = parse_hostport(args.replica_of)
        src = SocketTransport(ph, pp, expect_partition=partition_label)
        copy_chunk = 1024
        for lo in range(0, num_rows, copy_chunk):
            lids = np.arange(lo, min(lo + copy_chunk, num_rows))
            leaves = src.request(ExportRowsRequest(lids)).leaves
            server.import_rows(lids, leaves)
        src.close()
        print(f"replica boot: copied {num_rows} rows from "
              f"{args.replica_of} (slot {partition_label})", flush=True)
    else:
        all_vals = rng.normal(size=(args.kb_entries, args.kb_dim)) \
            .astype(np.float32)
        # tiered banks bound the distinct rows one write may touch —
        # chunk the initial fill to fit the resident tier
        fill_vals = all_vals[fill_ids]
        chunk = (min(args.kb_resident_rows, num_rows)
                 if args.kb_resident_rows else num_rows)
        for lo in range(0, num_rows, chunk):
            server.update(np.arange(lo, min(lo + chunk, num_rows)),
                          fill_vals[lo:lo + chunk])
    server.warmup(args.batch * args.clients)
    refresher = None
    if args.kb_search == "ivf":
        # index maker: clusters the bank off the serving path. On the
        # sharded backend this maintains one sub-index per shard and
        # rebuilds stale shards independently (repro.core.ann_index).
        refresher = server.start_ann_refresher(min_period_s=0.01)
        deadline = time.time() + 120.0
        while server.engine.ann_index is None:   # first build, then serve
            if refresher.last_error is not None or not refresher.is_alive():
                raise RuntimeError("IVF index build failed") \
                    from refresher.last_error
            if time.time() > deadline:
                raise RuntimeError("IVF index build timed out")
            time.sleep(0.01)

    # pre-compile the nn_search program too (warmup() covers only the
    # lookup/lazy_grad buckets) so no first-request jit stall is timed
    server.nn_search(np.zeros((args.batch, args.kb_dim), np.float32), k=8)

    runtime = None
    if args.kb_makers:
        # trainer-less serving can still host the checkpoint-free makers
        # (graph_builder): background engine clients maintaining the
        # dynamic neighbor graph while the bank serves. Paced (never
        # free-running): maker traffic shares the server, so an unpaced
        # maker would skew the timed client metrics below
        runtime = MakerRuntime(server, num_entries=args.kb_entries)
        for kind in args.kb_makers.split(","):
            runtime.register(kind.strip(), batch_size=args.batch,
                             min_period_s=args.kb_maker_period)
        runtime.start()

    client_errors = []
    if args.listen:
        # -- wire-serving mode: host the bank for OTHER processes ---------
        from repro.core import KBTransportServer, parse_hostport
        from repro.core.kb_protocol import PROTOCOL_VERSION
        host, port = parse_hostport(args.listen)
        transport = KBTransportServer(
            server, host, port,
            max_inflight=args.max_inflight,
            max_inflight_control=args.max_inflight_control or None,
            max_inflight_bulk=args.max_inflight_bulk or None,
            cork_us=args.cork_us, scheduler=args.scheduler,
            sock_buf=args.sock_buf, partition=partition_label)
        part = (f"partition {partition_label}, {num_rows} of "
                f"{args.kb_entries} rows, " if partition_label else "")
        print(f"kb server listening on {transport.host}:{transport.port} "
              f"(protocol v{PROTOCOL_VERSION}, backend={args.kb_backend}, "
              f"{part}bank {args.kb_entries}x{args.kb_dim}, "
              f"search={args.kb_search})", flush=True)
        stop = threading.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            signal.signal(sig, lambda *_: stop.set())
        stop.wait(args.serve_seconds or None)
        conns = transport.connections_accepted
        wire_reqs = transport.requests_served
        sendalls = transport.sendalls
        transport.close()
        summary = (f"{conns} connections, {wire_reqs} wire requests "
                   f"({sendalls} sendalls), ")
    else:
        # -- local-driver mode: synthetic concurrent in-process clients ---
        def client(t: int, n_calls: int):
            crng = np.random.default_rng(args.seed + 1 + t)
            try:
                for _ in range(n_calls):
                    ids = crng.integers(0, args.kb_entries, (args.batch,))
                    vals = server.lookup(ids)
                    server.lazy_grad(ids, 0.01 * vals)
                    server.nn_search(vals, k=8)
            except Exception as e:      # reported after the summary
                client_errors.append(e)

        threads = [threading.Thread(target=client, args=(t, args.gen))
                   for t in range(args.clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        dt = time.perf_counter() - t0
        calls = args.clients * args.gen * 3
        summary = (f"clients={args.clients}: {calls / dt:.0f} req/s "
                   f"({dt / calls * 1e6:.0f} us/req), ")
    stats = dict(server.engine.search_stats)
    refresh_error = refresher.last_error if refresher else None
    rebuilds = refresher.rebuilds if refresher else 0
    shard_rebuilds = refresher.shard_rebuilds if refresher else 0
    maker_stats = {}
    if runtime is not None:
        runtime.stop()
        maker_stats = server.maker_stats
    index = server.engine.ann_index
    server.close()
    print(f"kb-serve backend={args.kb_backend} search={args.kb_search} "
          f"coalesce={not args.no_coalesce} {summary}"
          f"coalescing x{server.coalescing_factor:.1f}, "
          f"{server.metrics['dispatches']} device dispatches for "
          f"{server.metrics['requests']} requests, "
          f"search_stats={stats}, "
          f"index rebuilds={rebuilds} ({shard_rebuilds} shard builds)",
          flush=True)
    sst = server.engine.storage_stats()
    print(f"kb storage mode={sst['mode']} bytes/row={sst['bytes_per_row']} "
          f"resident={sst['resident_rows']}/{sst['total_rows']} rows "
          f"(cold={sst['cold_rows']}), "
          f"bytes_resident={sst['bytes_resident']}, "
          f"cache hits/misses={server.metrics['cache_hits']}"
          f"/{server.metrics['cache_misses']}, "
          f"tier faults/spills={sst['tier_faults']}/{sst['tier_spills']}",
          flush=True)
    print(format_kb_timing(server.stats()["metrics"]), flush=True)
    for line in format_maker_stats(maker_stats):
        print(line)
    if index is not None and hasattr(index, "shard_stats"):
        # per-shard bucket skew: cap vs mean occupancy. headroom->0 marks
        # the shard whose next rebuild forces a full repack
        for st in index.shard_stats():
            print(f"ivf shard {st['shard']}: cap={st['bucket_cap']} "
                  f"mean_occ={st['mean_occupancy']:.1f} "
                  f"max_occ={st['max_occupancy']} "
                  f"skew=x{st['skew']:.2f} headroom={st['headroom']}")
    elif index is not None:
        st = index.bucket_stats()
        print(f"ivf buckets: cap={st['bucket_cap']} "
              f"mean_occ={st['mean_occupancy']:.1f} "
              f"max_occ={st['max_occupancy']} skew=x{st['skew']:.2f} "
              f"headroom={st['headroom']}")
    if client_errors:
        raise RuntimeError(
            f"{len(client_errors)} serving client(s) failed") \
            from client_errors[0]
    if refresh_error is not None:
        raise RuntimeError("IVF index refresher failed") from refresh_error
    return {"search_stats": stats,
            "requests": server.metrics["requests"],
            "dispatches": server.metrics["dispatches"],
            "index_rebuilds": rebuilds,
            "interpret": getattr(server.engine.backend, "interpret", None)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="yi-6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kb", action="store_true",
                    help="serve the knowledge bank instead of the LM")
    ap.add_argument("--kb-backend", choices=["dense", "pallas", "sharded"],
                    default="dense")
    ap.add_argument("--kb-entries", type=int, default=4096)
    ap.add_argument("--kb-dim", type=int, default=64)
    ap.add_argument("--kb-storage", choices=["fp32", "int8"], default="fp32",
                    help="bank row storage: fp32, or int8 codes + per-row "
                         "fp32 scale/offset with dequant fused into the "
                         "serving kernels (~3.5x less row memory)")
    ap.add_argument("--kb-cache-rows", type=int, default=0,
                    help="hot-id LRU capacity (rows) in front of the "
                         "engine; 0 disables the cache")
    ap.add_argument("--kb-resident-rows", type=int, default=None,
                    help="two-tier mode: keep only this many rows "
                         "device-resident; the rest spill to the cold "
                         "store and fault back on first touch")
    ap.add_argument("--kb-cold-after", type=int, default=None,
                    help="proactively spill rows untouched for this many "
                         "written rows (requires --kb-resident-rows)")
    ap.add_argument("--kb-cold-dir", default="",
                    help="cold-tier spill directory (default: host RAM)")
    ap.add_argument("--kb-search", choices=["exact", "ivf"], default="exact",
                    help="nn_search mode; ivf serves from the background-"
                         "clustered index (exact fallback until built)")
    ap.add_argument("--nlist", type=int, default=64,
                    help="IVF partitions (k-means centroids)")
    ap.add_argument("--nprobe", type=int, default=8,
                    help="IVF partitions probed per query")
    ap.add_argument("--kb-autotuned", default="", metavar="PATH",
                    help="load the ANN sweep result written by "
                         "tools/autotune_ann.py and override "
                         "--nlist/--nprobe with the winning config for "
                         "the active --kb-storage mode")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--kb-makers", default="",
                    help="comma list of checkpoint-free maker kinds (e.g. "
                         "graph_builder) to run as background engine "
                         "clients while serving; their counters print "
                         "with the serve summary (their traffic shares "
                         "the server, so the timed req/s includes the "
                         "maker load)")
    ap.add_argument("--kb-maker-period", type=float, default=0.05,
                    help="pacing floor (s) for --kb-makers jobs; keeps "
                         "background makers from saturating the timed "
                         "serving window")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="per-call locked baseline (benchmark ablation)")
    ap.add_argument("--kb-partitions", type=int, default=1,
                    help="split the id space over this many in-process "
                         "partition servers behind a KBRouter and drive "
                         "the router (scale-out rehearsal; incompatible "
                         "with --listen — use --kb-join for a wire fleet)")
    ap.add_argument("--kb-join", default="", metavar="I/N",
                    help="be partition I of an N-member fleet: host only "
                         "the ring slot's rows of the GLOBAL --kb-entries "
                         "bank and label the handshake I/N (requires "
                         "--listen); routers connect all members with "
                         "--kb-connect host:p0,host:p1,... in ring order")
    ap.add_argument("--kb-replicas", type=int, default=0,
                    help="--kb-partitions: replicas per in-process "
                         "partition — the first is a warm standby attached "
                         "to the router (filled by row export/import, kept "
                         "in sync by the write tee), the rest queue as "
                         "cold spares auto-attached after a promotion; "
                         "the wire-fleet equivalent is one --replica-of "
                         "process per member")
    ap.add_argument("--replica-of", default="", metavar="HOST:PORT",
                    help="boot as the standby of the fleet member at "
                         "HOST:PORT: size to the same --kb-join ring slot, "
                         "copy its full row state (every leaf, bit-"
                         "identically), then serve — a router attaches it "
                         "with attach_standby / the host:pN|host:sbN "
                         "--kb-connect syntax and promotes it if the "
                         "primary dies")
    ap.add_argument("--kb-reorder", action="store_true",
                    help="cross-op reordering in the coalescing "
                         "dispatcher: commuting requests (disjoint-id "
                         "writes, any lookups) hoist across the queue "
                         "into bigger batched dispatches")
    ap.add_argument("--listen", default="", metavar="HOST:PORT",
                    help="expose the bank on the TCP wire protocol for "
                         "cross-process trainers/makers (port 0 = "
                         "ephemeral, printed on startup) instead of "
                         "driving synthetic local clients")
    ap.add_argument("--serve-seconds", type=float, default=0.0,
                    help="--listen: exit after this long (0 = until "
                         "SIGINT/SIGTERM)")
    ap.add_argument("--max-inflight", type=int, default=32,
                    help="--listen: pipelining credits per connection "
                         "PER LANE (unanswered requests before the reader "
                         "applies TCP backpressure)")
    ap.add_argument("--max-inflight-control", type=int, default=0,
                    help="--listen: override the control lane's credits "
                         "(0 = same as --max-inflight)")
    ap.add_argument("--max-inflight-bulk", type=int, default=0,
                    help="--listen: override the bulk lane's credits "
                         "(0 = same as --max-inflight)")
    ap.add_argument("--cork-us", type=int, default=0,
                    help="--listen: adaptive writer-side cork window in "
                         "microseconds — hold a response batch up to this "
                         "long while more responses are in flight, packing "
                         "small frames into one sendall (0 = off)")
    ap.add_argument("--scheduler", choices=("lanes", "fifo"),
                    default="lanes",
                    help="--listen: response scheduler — 'lanes' (v4 "
                         "weighted priority, control > point > bulk) or "
                         "'fifo' (v3-style arrival order, the ablation "
                         "baseline)")
    ap.add_argument("--sock-buf", type=int, default=0,
                    help="--listen: SO_SNDBUF/SO_RCVBUF bytes "
                         "(0 = OS default)")
    add_device_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    apply_device_args(args)

    if args.kb:
        if args.kb_autotuned:
            from repro.core.ann_autotune import load_autotune
            tuned = load_autotune(args.kb_autotuned,
                                  storage=args.kb_storage)
            args.kb_search = "ivf"
            args.nlist, args.nprobe = tuned["nlist"], tuned["nprobe"]
            print(f"autotuned ANN config ({args.kb_storage}): "
                  f"nlist={args.nlist} nprobe={args.nprobe} "
                  f"recall@10={tuned['recall']:.3f}", flush=True)
            if not tuned.get("meets_floor", True):
                print("WARNING: no swept config cleared the recall "
                      "floor; serving the best-recall cell anyway — "
                      "widen the autotuner grid", file=sys.stderr,
                      flush=True)
        if args.kb_replicas and args.kb_partitions <= 1:
            ap.error("--kb-replicas pairs with --kb-partitions N (wire "
                     "fleets boot standbys with --replica-of instead)")
        if args.kb_partitions > 1:
            if args.listen:
                ap.error("--kb-partitions drives an in-process router; "
                         "to expose a partitioned fleet on the wire run "
                         "one process per partition with --kb-join I/N "
                         "--listen")
            if args.kb_makers or args.kb_search == "ivf":
                ap.error("--kb-partitions supports the plain serving "
                         "drive (no --kb-makers/--kb-search ivf yet)")
            serve_kb_partitioned(args)
            return None
        return serve_kb(args)

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    dist = DistContext()
    params = model.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    B = args.batch
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (B, args.prompt_len)), jnp.int32)
    extra = {}
    if cfg.frontend == "vision":
        extra["patch_embs"] = jnp.zeros(
            (B, cfg.num_frontend_tokens, cfg.d_model), jnp.float32)
    if cfg.frontend == "audio":
        extra["frames"] = jnp.zeros(
            (B, cfg.num_frontend_tokens, cfg.d_model), jnp.float32)

    t0 = time.perf_counter()
    cache, _ = model.prefill(params, toks, extra, dist,
                             cache_len=args.prompt_len + args.gen +
                             (cfg.num_frontend_tokens
                              if cfg.frontend == "vision" else 0) + 1)
    jax.block_until_ready(cache["t"])
    t_prefill = time.perf_counter() - t0

    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, extra, dist))
    last = toks[:, -1:]
    out = []
    t0 = time.perf_counter()
    for _ in range(args.gen):
        logits, cache = step(params, cache, last)
        last = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(last))
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0
    gen = np.concatenate(out, axis=1)
    print(f"arch={cfg.name} prefill({B}x{args.prompt_len})={t_prefill*1e3:.0f}ms"
          f" decode {args.gen} tok: {t_decode/args.gen*1e3:.1f} ms/tok")
    print("generated:", gen[0].tolist())


if __name__ == "__main__":
    main()
