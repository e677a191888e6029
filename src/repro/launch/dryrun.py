import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, prove memory/sharding coherence, and extract the
roofline terms (compute / memory / collective) from the compiled HLO.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results.jsonl

Each record contains compiled.memory_analysis() (proves it fits — or reports
exactly how far over budget a config is), compiled.cost_analysis(), and the
call-graph-walked per-device FLOPs / HBM bytes / collective wire bytes (see
hlo_analysis.py for why cost_analysis alone is insufficient for scanned
models)."""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, get_shape
from repro.configs.base import InputShape, ModelConfig
from repro.core import kb_create, kb_pspecs, make_carls_train_step
from repro.launch import specs as S
from repro.launch.hlo_analysis import (analyze_hlo, roofline_from_cost,
                                       V5E_HBM_BW, V5E_ICI_BW,
                                       V5E_PEAK_FLOPS)
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.sharding.partition import (DistContext, cache_pspecs, make_dist,
                                      param_pspecs)

DRYRUN_KB_ENTRIES = 1 << 20      # production-scale bank: 1M rows, 512-way


def dryrun_config(arch: str) -> ModelConfig:
    cfg = get_config(arch)
    return cfg.replace(carls=dataclasses.replace(
        cfg.carls, kb_entries=DRYRUN_KB_ENTRIES))


def make_optimizer(cfg: ModelConfig) -> AdamW:
    big = cfg.param_count() > 50e9
    return AdamW(lr=warmup_cosine(3e-4, 2000, 100_000),
                 moments_dtype="bfloat16" if big else "float32")


def _shardings(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# lowerings
# ---------------------------------------------------------------------------

def lower_train(cfg: ModelConfig, shape: InputShape, dist: DistContext):
    model = build_model(cfg)
    opt = make_optimizer(cfg)
    params_s = jax.eval_shape(model.init, jax.random.key(0))
    opt_s = jax.eval_shape(opt.init, params_s)
    kb_s = jax.eval_shape(
        lambda: kb_create(cfg.carls.kb_entries, cfg.d_model,
                          dtype=jnp.dtype(cfg.dtype)))
    p_spec = param_pspecs(params_s, cfg, dist)
    opt_spec = type(opt_s)(count=P(), mu=p_spec, nu=p_spec)
    kb_spec = kb_pspecs(dist)
    batch_s = S.train_batch_specs(cfg, shape)
    batch_sh = S.train_batch_shardings(cfg, shape, dist)
    step = make_carls_train_step(model, opt, dist)
    jitted = jax.jit(
        step,
        in_shardings=(_shardings(p_spec, dist.mesh),
                      _shardings(opt_spec, dist.mesh),
                      _shardings(kb_spec, dist.mesh),
                      batch_sh),
        out_shardings=(_shardings(p_spec, dist.mesh),
                       _shardings(opt_spec, dist.mesh),
                       _shardings(kb_spec, dist.mesh),
                       None),
        donate_argnums=(0, 1, 2),
    )
    return jitted.lower(params_s, opt_s, kb_s, batch_s)


def lower_prefill(cfg: ModelConfig, shape: InputShape, dist: DistContext):
    model = build_model(cfg)
    params_s = jax.eval_shape(model.init, jax.random.key(0))
    p_spec = param_pspecs(params_s, cfg, dist)
    tokens_s, extra_s = S.prefill_specs(cfg, shape)
    inp_sh = S.batch_shardings_for({"tokens": tokens_s, **extra_s}, cfg,
                                   shape.global_batch, dist)

    def prefill_step(params, tokens, extra):
        h, prefix, _, cache_ys = model.hidden(params, tokens, extra, dist,
                                              collect_cache=True)
        logits = h[:, -1:] @ model.out_embed(params).T
        return logits, cache_ys

    jitted = jax.jit(prefill_step,
                     in_shardings=(_shardings(p_spec, dist.mesh),
                                   inp_sh["tokens"],
                                   {k: inp_sh[k] for k in extra_s}))
    return jitted.lower(params_s, tokens_s, extra_s)


def lower_decode(cfg: ModelConfig, shape: InputShape, dist: DistContext):
    model = build_model(cfg)
    params_s = jax.eval_shape(model.init, jax.random.key(0))
    p_spec = param_pspecs(params_s, cfg, dist)
    cache_s, token_s, extra_s = S.decode_specs(cfg, shape, model)
    c_spec = cache_pspecs(cache_s, cfg, dist, shape.global_batch)
    tok_sh = S.batch_shardings_for({"t": token_s}, cfg, shape.global_batch,
                                   dist)["t"]
    extra_sh = S.batch_shardings_for(extra_s, cfg, shape.global_batch, dist)

    def serve_step(params, cache, token, extra):
        return model.decode_step(params, cache, token, extra, dist)

    jitted = jax.jit(serve_step,
                     in_shardings=(_shardings(p_spec, dist.mesh),
                                   _shardings(c_spec, dist.mesh),
                                   tok_sh, extra_sh),
                     out_shardings=(None, _shardings(c_spec, dist.mesh)),
                     donate_argnums=(1,))
    return jitted.lower(params_s, cache_s, token_s, extra_s)


def lower_maker(cfg: ModelConfig, shape: InputShape, dist: DistContext):
    """The knowledge-maker program, compiled for the same mesh — proof that
    a detached pod can run makers against the identically-sharded bank."""
    from repro.core import make_embedding_refresh
    model = build_model(cfg)
    params_s = jax.eval_shape(model.init, jax.random.key(0))
    p_spec = param_pspecs(params_s, cfg, dist)
    kb_s = jax.eval_shape(
        lambda: kb_create(cfg.carls.kb_entries, cfg.d_model,
                          dtype=jnp.dtype(cfg.dtype)))
    kb_spec = kb_pspecs(dist)
    B = shape.global_batch
    ids_s = jax.ShapeDtypeStruct((B,), jnp.int32)
    toks_s = jax.ShapeDtypeStruct((B, shape.seq_len), jnp.int32)
    sh = S.batch_shardings_for({"ids": ids_s, "toks": toks_s}, cfg, B, dist)
    maker = make_embedding_refresh(model, dist)
    jitted = jax.jit(maker, in_shardings=(_shardings(p_spec, dist.mesh),
                                          _shardings(kb_spec, dist.mesh),
                                          sh["ids"], sh["toks"]),
                     out_shardings=_shardings(kb_spec, dist.mesh),
                     donate_argnums=(1,))
    return jitted.lower(params_s, kb_s, ids_s, toks_s)


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def model_flops_analytic(cfg: ModelConfig, shape: InputShape) -> float:
    """MODEL_FLOPS: 6*N*D train (fwd+bwd), 2*N*D prefill, 2*N_active decode."""
    n_act = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_act * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len
    return 2.0 * n_act * shape.global_batch  # decode: 1 token


def analyze(lowered, compiled, cfg: ModelConfig, shape: InputShape,
            dist: DistContext) -> Dict:
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    cost = analyze_hlo(compiled.as_text(), dist.num_devices)
    mf = model_flops_analytic(cfg, shape) / dist.num_devices
    rl = roofline_from_cost(cost, model_flops_per_device=mf)
    hbm_gib = 16.0
    dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.generated_code_size_in_bytes)
    return {
        "arch": cfg.name, "shape": shape.name,
        "mesh": tuple(int(dist.mesh.shape[a]) for a in dist.mesh.axis_names),
        "devices": dist.num_devices,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_per_device_bytes": int(dev_bytes),
            "peak_per_device_gib": round(dev_bytes / 2**30, 3),
            "fits_16gib": bool(dev_bytes <= hbm_gib * 2**30),
        },
        "xla_cost_analysis": {
            "flops_while_bodies_once": float(ca.get("flops", 0.0)),
            "bytes_accessed_while_bodies_once":
                float(ca.get("bytes accessed", 0.0)),
        },
        "roofline": rl.to_dict(),
    }


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def choose_strategy(cfg: ModelConfig, shape: InputShape, devices: int) -> str:
    """Beyond-paper optimization (EXPERIMENTS §Perf-3): small dense models
    with device-divisible global batch train fastest as pure FSDP — batch
    over every mesh axis, per-layer weight gathering, no tensor parallelism
    (3.5x lower collective term than FSDPxTPxSP for yi-6b train_4k)."""
    return ("fsdp" if (shape.kind == "train"
                       and not cfg.is_moe
                       and not cfg.cross_attention
                       and cfg.param_count() < 50e9
                       and shape.global_batch % devices == 0
                       and cfg.d_model % devices == 0)
            else "tp")


def run_one(arch: str, shape_name: str, multi_pod: bool,
            program: str = "auto", strategy: str = "auto") -> Dict:
    cfg = dryrun_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    dist = make_dist(mesh)
    if strategy == "auto":
        strategy = choose_strategy(cfg, shape, mesh.size)
    dist = dataclasses.replace(dist, strategy=strategy)
    if program == "auto":
        program = {"train": "train", "prefill": "prefill",
                   "decode": "decode"}[shape.kind]
    t0 = time.time()
    with mesh:
        if program == "train":
            lowered = lower_train(cfg, shape, dist)
        elif program == "prefill":
            lowered = lower_prefill(cfg, shape, dist)
        elif program == "maker":
            lowered = lower_maker(cfg, shape, dist)
        else:
            lowered = lower_decode(cfg, shape, dist)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    rec = analyze(lowered, compiled, cfg, shape, dist)
    rec.update(program=program, multi_pod=multi_pod, strategy=strategy,
               lower_s=round(t_lower, 1), compile_s=round(t_compile, 1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--program", default="auto",
                    choices=["auto", "train", "prefill", "decode", "maker"])
    ap.add_argument("--strategy", default="auto",
                    choices=["auto", "tp", "fsdp"])
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape), single-pod baseline")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args(argv)

    pairs = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                pairs.append((a, s, False))
                if args.both_meshes:
                    pairs.append((a, s, True))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape, args.multi_pod)]
        if args.both_meshes:
            pairs.append((args.arch, args.shape, True))

    failures = 0
    for arch, shp, mp in pairs:
        tag = f"{arch} x {shp} x {'2x16x16' if mp else '16x16'}"
        try:
            rec = run_one(arch, shp, mp, args.program, args.strategy)
            rl = rec["roofline"]
            print(f"[OK] {tag}: mem/dev={rec['memory']['peak_per_device_gib']}"
                  f" GiB fits={rec['memory']['fits_16gib']} "
                  f"compute={rl['compute_s']:.4f}s memory={rl['memory_s']:.4f}s"
                  f" collective={rl['collective_s']:.4f}s "
                  f"bottleneck={rl['bottleneck']} "
                  f"useful={rl['useful_ratio']:.2f} "
                  f"(compile {rec['compile_s']}s)", flush=True)
        except Exception as e:
            failures += 1
            rec = {"arch": arch, "shape": shp, "multi_pod": mp,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
