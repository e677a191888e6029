"""Paper §1/§4.1 headline claim: with CARLS, trainer step cost is ~flat in
the number of graph-regularization neighbors K (they are *looked up*), while
the conventional baseline that encodes neighbors in-trainer grows linearly.

Two measurements per K: wall-clock per step (CPU, small model) and compiled
per-step FLOPs (platform-independent; the shape of the curve is the claim).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import kb_create, make_carls_train_step, \
    make_inline_baseline_step
from repro.data import SyntheticGraphCorpus
from repro.models import build_model
from repro.optim import AdamW, constant_lr
from repro.sharding.partition import DistContext

DIST = DistContext()


def _time_steps(fn, args, reps=5):
    out = fn(*args)                      # compile
    jax.block_until_ready(jax.tree.leaves(out)[0])
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(jax.tree.leaves(out)[0])
    return (time.perf_counter() - t0) / reps


def run(quick: bool = False) -> List[Dict]:
    ks = [1, 4, 8] if quick else [1, 2, 4, 8, 16]
    cfg0 = get_config("yi-6b").reduced().replace(num_layers=2)
    opt = AdamW(lr=constant_lr(1e-3))
    corpus = SyntheticGraphCorpus(num_nodes=512, vocab_size=cfg0.vocab_size,
                                  seq_len=33, neighbors_per_node=max(ks))
    rng = np.random.default_rng(0)
    B = 4
    b = corpus.batch(rng, B)
    rows = []
    for K in ks:
        cfg = cfg0.replace(carls=cfg0.carls.__class__(
            **{**cfg0.carls.__dict__, "num_neighbors": K,
               "kb_entries": 512}))
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        kb = kb_create(512, cfg.d_model)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        jb["neighbor_ids"] = jnp.asarray(b["neighbor_ids"][:, :K])
        jb["neighbor_weights"] = jnp.asarray(b["neighbor_weights"][:, :K])

        step_c = jax.jit(make_carls_train_step(model, opt, DIST))
        t_carls = _time_steps(step_c, (params, opt.init(params), kb, jb))
        f_carls = step_c.lower(params, opt.init(params),
                               kb, jb).compile().cost_analysis()["flops"]

        jb2 = dict(jb)
        jb2["neighbor_tokens"] = jnp.asarray(
            corpus.neighbor_tokens(b["neighbor_ids"][:, :K]))
        step_b = jax.jit(make_inline_baseline_step(model, opt, DIST,
                                                   num_neighbors=K))
        t_base = _time_steps(step_b, (params, opt.init(params), jb2))
        f_base = step_b.lower(params, opt.init(params),
                              jb2).compile().cost_analysis()["flops"]
        rows.append({"name": f"neighbor_scaling/K={K}/carls",
                     "us_per_call": t_carls * 1e6,
                     "derived": f"flops={f_carls:.3g}"})
        rows.append({"name": f"neighbor_scaling/K={K}/inline_baseline",
                     "us_per_call": t_base * 1e6,
                     "derived": f"flops={f_base:.3g}"})
    return rows
