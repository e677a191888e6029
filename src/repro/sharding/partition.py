"""Sharding rules: path-based parameter partitioning + batch/cache specs.

Mesh axes:
- ``pod``   : pure data-parallel across pods (params *replicated* so a pod is
              self-sufficient — this is what lets CARLS detach a pod as a
              knowledge-maker fleet; see DESIGN.md §3).
- ``data``  : data-parallel + FSDP (params/moments sharded along it).
- ``model`` : tensor / expert / sequence parallel.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig


@dataclass(frozen=True)
class DistContext:
    mesh: Optional[Mesh] = None
    data_axis: str = "data"
    model_axis: str = "model"
    pod_axis: Optional[str] = None      # None on the single-pod mesh
    strategy: str = "tp"                # tp (FSDP x TP x SP) | fsdp (pure)

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        axes = ((self.pod_axis, self.data_axis) if self.pod_axis
                else (self.data_axis,))
        if self.strategy == "fsdp":     # batch over every axis
            axes = axes + (self.model_axis,)
        return axes

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis] if self.mesh else 1

    @property
    def dp_size(self) -> int:
        if not self.mesh:
            return 1
        n = self.mesh.shape[self.data_axis]
        if self.pod_axis:
            n *= self.mesh.shape[self.pod_axis]
        return n

    @property
    def num_devices(self) -> int:
        return self.mesh.size if self.mesh else 1

    def constrain(self, x, spec: P):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def sharding(self, spec: P) -> Optional[NamedSharding]:
        return NamedSharding(self.mesh, spec) if self.mesh else None


def make_dist(mesh: Optional[Mesh]) -> DistContext:
    if mesh is None:
        return DistContext()
    pod = "pod" if "pod" in mesh.axis_names else None
    return DistContext(mesh=mesh, pod_axis=pod)


def local_dist() -> DistContext:
    """A (1, n) ``data`` x ``model`` mesh over every local device: the
    mesh a sharded Knowledge Bank spans when its caller gives none."""
    devices = np.asarray(jax.local_devices()).reshape(1, -1)
    return DistContext(mesh=Mesh(devices, ("data", "model")))


# ---------------------------------------------------------------------------
# parameter partitioning (path-rule based, mirrors init structure by name)
# ---------------------------------------------------------------------------

def _rule(name: str, ndim: int, cfg: ModelConfig, dist: DistContext,
          stacked: bool):
    """PartitionSpec for a leaf called ``name`` with ``ndim`` dims.
    ``stacked``: leading scan-group dim present."""
    d, m = dist.data_axis, dist.model_axis
    ep = cfg.is_moe and (cfg.num_experts % max(dist.model_size, 1) == 0)
    lead = (None,) if stacked else ()

    in_proj = {"wq", "wk", "wv", "w_r", "w_k", "w_v", "w_g", "w_in",
               "wi", "wg", "dec_a"}
    out_proj = {"wo", "w_out", "w_o"}

    if dist.strategy == "fsdp":
        # pure FSDP: weights sharded over (data x model) on d_in, gathered
        # per layer; no tensor parallelism at all (small-dense train shapes)
        fs = (d, m)
        n_all = dist.model_size * max(dist.dp_size // dist.model_size, 1) \
            if dist.mesh else 1
        if name in ("tok", "out_embed"):
            if cfg.vocab_size % max(n_all, 1) == 0:
                return P(fs, None)
            return P(None, fs) if cfg.d_model % max(n_all, 1) == 0 else \
                P(None, None)
        if (name in in_proj or name in out_proj or name in
                ("wr", "w_xdb")) and ndim - len(lead) >= 2 \
                and name != "dec_a":     # lora mats (64-dim) stay replicated
            return P(*lead, fs, *([None] * (ndim - len(lead) - 1)))
        return P(*([None] * ndim))

    if name in ("tok", "out_embed"):
        # odd vocab sizes (whisper 51865, internvl 92553) can't shard evenly
        # over the model axis; shard the feature dim over 'data' instead
        if cfg.vocab_size % max(dist.model_size, 1) != 0:
            return P(None, d)
        return P(m, None)
    if name == "pos_embed":
        return P(None, None)
    if name in ("wr",):                      # router (D, E): replicate E
        return P(*lead, d, None)
    if name in ("moe_wi", "moe_wg"):         # (E, D, F)
        return (P(*lead, m, d, None) if ep else P(*lead, None, d, m))
    if name == "moe_wo":                     # (E, F, D)
        return (P(*lead, m, None, d) if ep else P(*lead, None, m, d))
    if name in in_proj and ndim - len(lead) == 2:
        return P(*lead, d, m)
    if name in out_proj and ndim - len(lead) == 2:
        return P(*lead, m, d)
    if name == "dec_b":                      # (lora, D): match k sharding
        return P(*lead, None, m)
    if name == "w_xdb":                      # (di, r+2ds)
        return P(*lead, m, None)
    if name == "w_dt":                       # (r, di)
        return P(*lead, None, m)
    if name == "conv":                       # (w, di)
        return P(*lead, None, m)
    if name in ("conv_b", "dt_bias", "d_skip", "dec_0", "ln_x"):
        return P(*lead, m)
    if name == "a_log":                      # (di, ds)
        return P(*lead, m, None)
    if name == "u":                          # (Hn, hd)
        return P(*lead, m, None)
    # norms, mu, scalars: replicated
    return P(*([None] * ndim))


def param_pspecs(params, cfg: ModelConfig, dist: DistContext):
    """PartitionSpec pytree matching ``params``."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def spec_for(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        name = names[-1]
        # moe weights are distinguished from dense ffn by their parent key
        if "moe" in names[:-1] or "ffn_moe" in names[:-1]:
            if name in ("wi", "wg", "wo"):
                name = "moe_" + name
        stacked = any(n in ("groups", "enc") for n in names[:2]) or \
            any(n.startswith("pos") for n in names)
        return _rule(name, leaf.ndim, cfg, dist, stacked)

    return jax.tree_util.tree_map_with_path(spec_for, params)


# ---------------------------------------------------------------------------
# batch / cache specs (input-shape dependent)
# ---------------------------------------------------------------------------

def batch_pspec(dist: DistContext, batch_size: int) -> P:
    """Spec for the leading batch dim; replicated when B < dp size."""
    if dist.mesh is None or batch_size % max(dist.dp_size, 1) != 0:
        return P(None)
    return P(dist.dp_axes)


def cache_pspecs(cache, cfg: ModelConfig, dist: DistContext, batch_size: int):
    """KV-cache / SSM-state specs. Attention caches (..., B, C, KV, hd):
    batch over dp when divisible, cache length sequence-parallel over
    'model' (plus 'data' for B=1 long-context)."""
    d, m = dist.data_axis, dist.model_axis
    bdp = batch_size % max(dist.dp_size, 1) == 0
    b_ax = dist.dp_axes if bdp else None
    seq_ax = m if bdp else (d, m)

    def spec_for(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        name = names[-1]
        if name in ("k", "v"):              # (G, B, C, KV, hd)
            return P(None, b_ax, seq_ax, None, None)
        if name == "pos":                   # (B, C) shared across layers
            return P(b_ax, seq_ax)
        if name in ("ck", "cv"):            # cross-attn cache (G,B,F,KV,hd)
            return P(None, b_ax, None, None, None)
        if name == "h":                     # mamba (G?, B, di, ds)
            return P(*([None] * (leaf.ndim - 3)), b_ax, m, None)
        if name == "S":                     # rwkv (G?, B, Hn, hd, hd)
            return P(*([None] * (leaf.ndim - 4)), b_ax, m, None, None)
        if name == "conv_buf":              # (G?, B, w, di)
            return P(*([None] * (leaf.ndim - 3)), b_ax, None, m)
        if name == "x_prev":                # (G?, B, D)
            return P(*([None] * (leaf.ndim - 2)), b_ax, None)
        if name == "t":
            return P()
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec_for, cache)
