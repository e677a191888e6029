"""Training launcher: end-to-end CARLS training on real devices.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
      --steps 100 --batch 8 --seq 64

``--reduced`` (the default) trains the CPU-sized variant of the family
(``ModelConfig.reduced()``); ``--no-reduced`` keeps the published widths,
with ``--layers`` cutting depth, which is how a chip runs it. The default
loop is the
in-graph CARLS step: KB lookup -> loss(CE + graph reg) -> lazy grad push ->
AdamW, with periodic checkpointing and a maker refresh pass
(synchronous-maker mode), all KB traffic through the ``KBOps`` facade.

``--makers`` switches to the paper's full asynchronous topology: the
trainer and a ``MakerRuntime`` fleet (any of embedding_refresh /
label_mining / graph_agreement / graph_builder) run concurrently as
clients of ONE request-coalescing ``KnowledgeBankServer``, and the run
ends with per-maker counters (maker_steps / rows_written /
ckpt_version_lag):

  PYTHONPATH=src python -m repro.launch.train --makers \
      label_mining,graph_agreement --steps 20 --batch 8

``--kb-connect HOST:PORT`` (async mode) sends the trainer's host-side KB
traffic — neighbor lookups, lazy gradient pushes, trainer-push updates, and
any ``--makers`` registered in this process — over the TCP wire protocol to
a bank hosted elsewhere (``launch/serve.py --kb --listen``), the paper's
cross-platform topology:

  PYTHONPATH=src python -m repro.launch.train --makers graph_builder \
      --kb-connect 127.0.0.1:7787 --steps 20 --batch 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import DiskCheckpointStore
from repro.configs import ARCH_IDS, get_config
from repro.env import add_device_args, apply_device_args, enable_compile_cache
from repro.core import (format_maker_stats, kb_create,
                        make_carls_train_step, make_embedding_refresh,
                        run_async_training)
from repro.data import SyntheticGraphCorpus
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.sharding.partition import DistContext


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="yi-6b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the CPU-sized variant (ModelConfig."
                         "reduced); --no-reduced keeps published widths")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--nodes", type=int, default=2048)
    ap.add_argument("--maker-every", type=int, default=10)
    ap.add_argument("--makers", default="",
                    help="comma list of async maker kinds (embedding_refresh"
                         ",label_mining,graph_agreement,graph_builder); "
                         "non-empty switches to the async trainer+"
                         "MakerRuntime topology over one coalescing server")
    ap.add_argument("--maker-batch", type=int, default=64)
    ap.add_argument("--maker-period", type=float, default=0.0,
                    help="per-maker pacing floor in seconds")
    ap.add_argument("--ckpt-period", type=int, default=5,
                    help="async mode: trainer steps between checkpoint "
                         "publishes (the data-freshness axis)")
    ap.add_argument("--kb-backend", choices=["dense", "pallas", "sharded"],
                    default="dense", help="async mode: bank engine backend")
    ap.add_argument("--kb-connect", default="",
                    metavar="HOST:PORT[,HOST:PORT,...]",
                    help="async mode: send all KB traffic to a remote bank "
                         "over the wire protocol (serve.py --kb --listen) "
                         "instead of hosting one in-process; a comma list "
                         "names a PARTITIONED fleet in ring order (one "
                         "serve.py --kb-join process per endpoint) routed "
                         "through a KBRouter transparently; host:p0|host:s0 "
                         "attaches s0 as partition 0's standby (promoted "
                         "on failure, see launch/fleet.py); --nodes must "
                         "not exceed the bank's total entries")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    add_device_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    apply_device_args(args)
    if args.kb_connect and not args.makers:
        # the sync in-graph loop owns its KBState and never talks to a
        # server — silently training against a local bank while the user
        # believes traffic goes remote would be the worst failure mode
        ap.error("--kb-connect requires the async topology: pass --makers "
                 "(e.g. --makers graph_builder)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    if args.d_model:
        cfg = cfg.replace(d_model=args.d_model,
                          head_dim=args.d_model // cfg.num_heads or 32)
    cfg = cfg.replace(carls=cfg.carls.__class__(
        **{**cfg.carls.__dict__, "kb_entries": args.nodes}))
    model = build_model(cfg)
    dist = DistContext()
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"(reduced={args.reduced})")

    if args.makers:
        return run_async(model, cfg, args)

    params = model.init(jax.random.key(args.seed))
    n_par = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"actual params: {n_par/1e6:.1f}M")
    opt = AdamW(lr=warmup_cosine(args.lr, args.steps // 10, args.steps),
                weight_decay=0.01)
    opt_state = opt.init(params)
    kb = kb_create(args.nodes, cfg.d_model, key=jax.random.key(1))
    corpus = SyntheticGraphCorpus(
        num_nodes=args.nodes, vocab_size=cfg.vocab_size,
        seq_len=args.seq + 1, neighbors_per_node=cfg.carls.num_neighbors)
    step_fn = jax.jit(make_carls_train_step(model, opt, dist),
                      donate_argnums=(0, 1, 2))
    maker_fn = jax.jit(make_embedding_refresh(model, dist),
                       donate_argnums=(1,))
    ckpts = DiskCheckpointStore(args.ckpt_dir) if args.ckpt_dir else None

    rng = np.random.default_rng(args.seed + 1)
    history = []                    # per-step device scalars, read at end
    maker_refreshes = 0
    t0 = time.perf_counter()
    for step in range(args.steps):
        b = corpus.batch(rng, args.batch)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt_state, kb, m = step_fn(params, opt_state, kb, jb)
        history.append((m["loss"], m["kb_pending"]))
        if (step + 1) % args.maker_every == 0:
            ids = rng.integers(0, args.nodes, args.batch).astype(np.int32)
            toks = corpus.node_tokens(ids)[:, :-1]
            kb = maker_fn(params, kb, jnp.asarray(ids), jnp.asarray(toks))
            maker_refreshes += 1
        if ckpts and (step + 1) % args.ckpt_every == 0:
            ckpts.save(step + 1, params)
        if step < 3 or (step + 1) % 10 == 0:
            print(f"step {step+1:5d} loss={float(m['loss']):.4f} "
                  f"acc={float(m['acc']):.3f} reg={float(m['graph_reg']):.4f}"
                  f" gnorm={float(m['grad_norm']):.2f}"
                  f" kb_pending={float(m['kb_pending']):.0f}", flush=True)
    jax.block_until_ready(kb)
    dt = time.perf_counter() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({dt/args.steps*1e3:.0f} ms/step), "
          f"{maker_refreshes} maker refreshes")
    return {"losses": [float(l) for l, _ in history],
            "kb_pending": [float(p) for _, p in history],
            "maker_refreshes": maker_refreshes,
            "layers": cfg.num_layers, "d_model": cfg.d_model,
            "params": n_par}


def run_async(model, cfg, args) -> None:
    """``--makers``: trainer + MakerRuntime concurrently against one
    coalescing KnowledgeBankServer (the paper's Figure-1 triangle)."""
    makers = [m.strip() for m in args.makers.split(",") if m.strip()]
    corpus = SyntheticGraphCorpus(
        num_nodes=args.nodes, vocab_size=cfg.vocab_size,
        seq_len=args.seq + 1, neighbors_per_node=cfg.carls.num_neighbors,
        num_clusters=4, labeled_frac=0.3, label_noise=0.3,
        seed=args.seed)
    kb_client = None
    if args.kb_connect:
        from repro.core import connect_kb
        kb_client = connect_kb(args.kb_connect, client_name="trainer")
        parts = getattr(kb_client, "pmap", None)
        shape = (f"{parts.num_partitions} partitions, " if parts else "")
        print(f"async CARLS: trainer + makers {makers} over the wire "
              f"(bank at {args.kb_connect}: {shape}"
              f"{kb_client.num_entries}x{kb_client.dim})")
    else:
        print(f"async CARLS: trainer + makers {makers} "
              f"(kb backend: {args.kb_backend})")
    t0 = time.perf_counter()
    res = run_async_training(
        model, corpus, steps=args.steps, batch_size=args.batch,
        makers=makers, maker_batch=args.maker_batch,
        maker_period_s=args.maker_period, ckpt_period=args.ckpt_period,
        lr=args.lr, trainer_push=True, kb_backend=args.kb_backend,
        kb_client=kb_client, seed=args.seed)
    dt = time.perf_counter() - t0
    print(f"loss {res.losses[0]:.4f} -> {np.mean(res.losses[-5:]):.4f} "
          f"over {args.steps} steps in {dt:.1f}s; "
          f"mean row staleness {res.mean_staleness:.2f} trainer steps")
    m = res.server.metrics
    print(f"kb server: {m['requests']} requests -> {m['dispatches']} "
          f"dispatches (coalescing x{res.server.coalescing_factor:.1f})")
    if kb_client is not None:
        t = res.server.stats().get("transport", {})
        if t:
            print(f"kb transport: reconnects={t.get('reconnects', 0)} "
                  f"reissued={t.get('reissued', 0)}")
    for line in format_maker_stats(res.server.maker_stats):
        print(line)


if __name__ == "__main__":
    main()
