"""Mixture-of-Experts FFN.

Three execution paths sharing identical routing semantics (top-k softmax
gating over E experts):

- ``moe_ref``          : dense all-experts reference (exact, no drops). Used
                         by smoke tests and as the oracle.
- ``moe_capacity``     : capacity-based dispatch (sort -> (E, C) slot table ->
                         gather -> batched expert matmul -> scatter-combine).
                         FLOPs ~= 1.25 x active. Train / prefill path.
- ``moe_slot_gather``  : per-assignment expert-weight gather. FLOPs and HBM
                         bytes exactly match real MoE decode (weights of the
                         touched experts are read once per assignment). Decode
                         path (few tokens per shard).

``moe_sharded`` wraps these in shard_map over the production mesh with two
sharding modes:
- EP  (num_experts % model_axis == 0): experts sharded over 'model'; foreign
      assignments masked locally, outputs combined with a psum over 'model'.
- TP  (otherwise, e.g. grok's 8 experts on 16-way model axis): experts
      replicated, d_ff sharded over 'model'; classic Megatron psum.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

CAPACITY_FACTOR = 1.25


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def route(x: jnp.ndarray, wr: jnp.ndarray, k: int):
    """x: (T, D); wr: (D, E) -> gates (T, K) fp32, experts (T, K) int32,
    plus router aux loss (load-balancing, Switch-style)."""
    logits = (x.astype(jnp.float32) @ wr.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    gates, experts = jax.lax.top_k(probs, k)                # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss: E * sum_e f_e * p_e
    E = wr.shape[-1]
    me = probs.mean(0)                                       # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(
        1.0 / experts.size)
    aux = E * jnp.sum(me * ce)
    return gates, experts, aux


# ---------------------------------------------------------------------------
# reference path (exact, dense over experts)
# ---------------------------------------------------------------------------

def moe_ref(x, wr, wi, wg, wo, k: int):
    """x: (T, D). Computes every expert for every token, combines with the
    exact top-k gates. O(T*E*D*F) — small configs only."""
    T, D = x.shape
    E = wr.shape[-1]
    gates, experts, aux = route(x, wr, k)
    h = jnp.einsum("td,edf->tef", x, wg)
    h = jax.nn.silu(h) * jnp.einsum("td,edf->tef", x, wi)
    y_all = jnp.einsum("tef,efd->ted", h, wo)               # (T, E, D)
    dense_gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], experts].add(gates)
    y = jnp.einsum("ted,te->td", y_all.astype(jnp.float32), dense_gates)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# capacity dispatch (train / prefill)
# ---------------------------------------------------------------------------

def _slot_tables(experts, gates, num_experts: int, capacity: int,
                 owner_mask=None):
    """Build (E, C) token-index and gate tables from (T, K) assignments.

    owner_mask: optional (T, K) bool — assignments not owned by this shard
    are routed to a trash expert id E (dropped).
    """
    T, K = experts.shape
    flat_e = experts.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    if owner_mask is not None:
        flat_e = jnp.where(owner_mask.reshape(-1), flat_e, num_experts)
    order = jnp.argsort(flat_e, stable=True)
    se, sg, st = flat_e[order], flat_g[order], flat_t[order]
    # rank within expert = position - first position of that expert
    pos = jnp.arange(T * K)
    is_start = jnp.concatenate([jnp.array([True]), se[1:] != se[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum,
                                         jnp.where(is_start, pos, 0))
    rank = pos - seg_start
    keep = (rank < capacity) & (se < num_experts)
    # scatter into (E, C); dropped assignments use out-of-range indices so
    # mode="drop" discards them instead of clobbering live slots
    tok_tbl = jnp.full((num_experts, capacity), T, jnp.int32)   # T = pad row
    gate_tbl = jnp.zeros((num_experts, capacity), jnp.float32)
    e_idx = jnp.where(keep, se, num_experts)
    c_idx = jnp.where(keep, rank, capacity)
    tok_tbl = tok_tbl.at[e_idx, c_idx].set(st.astype(jnp.int32), mode="drop")
    gate_tbl = gate_tbl.at[e_idx, c_idx].set(sg, mode="drop")
    dropped = (~keep & (se < num_experts)).sum()
    return tok_tbl, gate_tbl, dropped


def moe_capacity(x, wi, wg, wo, tok_tbl, gate_tbl):
    """x: (T, D); expert weights (E?, D, F); tables (E?, C). Returns (T, D)
    partial output (zeros where this shard owns nothing)."""
    T, D = x.shape
    xp = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], 0)   # pad row
    xe = xp[tok_tbl]                                           # (E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
    h = h * jnp.einsum("ecd,edf->ecf", xe, wi)
    ye = jnp.einsum("ecf,efd->ecd", h, wo)
    ye = ye * gate_tbl[..., None].astype(ye.dtype)
    out = jnp.zeros((T + 1, D), jnp.float32).at[tok_tbl].add(
        ye.astype(jnp.float32))
    return out[:T].astype(x.dtype)


# ---------------------------------------------------------------------------
# slot-gather dispatch (decode)
# ---------------------------------------------------------------------------

def moe_slot_gather(x, wi, wg, wo, experts, gates, num_slots: int,
                    owner_mask=None, expert_offset: int = 0):
    """Per-assignment expert weight gather. x: (T, D); experts/gates (T, K).

    num_slots: static slot budget (>= expected local assignments). Each slot
    reads its expert's (D, F) weights — honest decode memory traffic.
    """
    T, K = experts.shape
    E_loc = wi.shape[0]
    flat_e = experts.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    valid = jnp.ones((T * K,), bool)
    if owner_mask is not None:
        valid = owner_mask.reshape(-1)
    # compact owned assignments to the front, take num_slots of them
    order = jnp.argsort(jnp.where(valid, 0, 1), stable=True)
    se = (flat_e[order] - expert_offset)[:num_slots]
    sg = flat_g[order][:num_slots]
    st = flat_t[order][:num_slots]
    sv = valid[order][:num_slots]
    se = jnp.clip(se, 0, E_loc - 1)
    xp = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], 0)
    xs = xp[jnp.where(sv, st, T)]                              # (S, D)
    wgs, wis, wos = wg[se], wi[se], wo[se]                     # (S, D, F)
    h = jax.nn.silu(jnp.einsum("sd,sdf->sf", xs, wgs))
    h = h * jnp.einsum("sd,sdf->sf", xs, wis)
    ys = jnp.einsum("sf,sfd->sd", h, wos)
    ys = ys * (sg * sv)[:, None].astype(ys.dtype)
    out = jnp.zeros((T + 1, x.shape[1]), jnp.float32).at[
        jnp.where(sv, st, T)].add(ys.astype(jnp.float32))
    return out[:T].astype(x.dtype)


# ---------------------------------------------------------------------------
# sharded front-end
# ---------------------------------------------------------------------------

def _local_moe(x, wr, wi, wg, wo, *, cfg: ModelConfig, expert_parallel: bool,
               model_axis: Optional[str], decode: bool):
    """Body executed per (dp x model) shard inside shard_map (or unsharded
    when model_axis is None)."""
    T = x.shape[0]
    K = cfg.experts_per_token
    E = cfg.num_experts
    gates, experts, aux = route(x, wr, K)
    if model_axis is not None and expert_parallel:
        n_model = jax.lax.axis_size(model_axis)
        midx = jax.lax.axis_index(model_axis)
        e_loc = E // n_model
        owner = (experts // e_loc) == midx
        offset = midx * e_loc
    else:
        n_model = (jax.lax.axis_size(model_axis)
                   if model_axis is not None else 1)
        owner, offset, e_loc = None, 0, E

    if decode:
        share = 1.0 / n_model if expert_parallel else 1.0
        slots = max(8, int(math.ceil(T * K * share * 1.5)))
        slots = min(slots, T * K)
        y = moe_slot_gather(x, wi, wg, wo, experts, gates, slots,
                            owner_mask=owner, expert_offset=offset)
    else:
        cap = max(1, int(math.ceil(T * K / E * CAPACITY_FACTOR)))
        if owner is not None:
            experts_l = jnp.where(owner, experts - offset, e_loc)
            tok_tbl, gate_tbl, _ = _slot_tables(experts_l, gates, e_loc, cap)
        else:
            tok_tbl, gate_tbl, _ = _slot_tables(experts, gates, E, cap)
        y = moe_capacity(x, wi, wg, wo, tok_tbl, gate_tbl)

    if model_axis is not None:
        # EP: combine expert outputs across shards. TP: classic partial-sum.
        y = jax.lax.psum(y, model_axis)
        aux = jax.lax.pmean(aux, model_axis)
    return y, aux


def _decode_moe_sharded(x, wr, wi, wg, wo, *, cfg: ModelConfig, ep: bool,
                        dist, dp):
    """Decode-path MoE with expert weights kept FULLY SHARDED in place.

    At decode the token set is tiny (B x 1) while expert weights are huge, so
    the right data movement is: all-gather the *tokens* over 'data' (KBs),
    compute slot-gathered expert matmuls against the local (D over 'data',
    [F over 'model' in TP mode]) weight shards, and reduce the partials —
    instead of shard_map's implicit per-layer all-gather of the weights
    (which was 252 GiB/step for kimi-k2 decode_32k — see EXPERIMENTS §Perf).
    """
    da, ma = dist.data_axis, dist.model_axis
    E, K = cfg.num_experts, cfg.experts_per_token
    Tl, D = x.shape
    if dp is not None:
        x = jax.lax.all_gather(x, da, axis=0, tiled=True)   # (T, D) tiny
    T = x.shape[0]
    gates, experts, aux = route(x, wr, K)
    n_model = jax.lax.axis_size(ma)
    n_data = jax.lax.axis_size(da)
    if ep:
        e_loc = E // n_model
        midx = jax.lax.axis_index(ma)
        owner = (experts // e_loc) == midx
        offset = midx * e_loc
        share = 1.0 / n_model
    else:
        owner, offset, share = None, 0, 1.0
    slots = min(max(8, int(math.ceil(T * K * share * 1.5))), T * K)

    flat_e = experts.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    valid = owner.reshape(-1) if owner is not None else \
        jnp.ones((T * K,), bool)
    order = jnp.argsort(jnp.where(valid, 0, 1), stable=True)
    se = jnp.clip((flat_e[order] - offset)[:slots], 0, wi.shape[0] - 1)
    sg = flat_g[order][:slots]
    st = flat_t[order][:slots]
    sv = valid[order][:slots]

    D_loc = wi.shape[1]
    d0 = jax.lax.axis_index(da) * D_loc
    xp = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], 0)
    xs = jax.lax.dynamic_slice(xp[jnp.where(sv, st, T)], (0, d0),
                               (slots, D_loc))               # (S, D_loc)
    wgs, wis, wos = wg[se], wi[se], wo[se]   # (S, D_loc, F?), (S, F?, D_loc)
    hg = jax.lax.psum(jnp.einsum("sd,sdf->sf", xs, wgs), da)  # complete D
    hi = jax.lax.psum(jnp.einsum("sd,sdf->sf", xs, wis), da)
    h = jax.nn.silu(hg) * hi
    ye = jnp.einsum("sf,sfd->sd", h, wos)    # (S, D_loc) [partial over F: TP]
    ye = ye * (sg * sv)[:, None].astype(ye.dtype)
    out = jnp.zeros((T + 1, D_loc), jnp.float32).at[
        jnp.where(sv, st, T)].add(ye.astype(jnp.float32))[:T]
    out = jax.lax.psum(out, ma)              # EP: experts; TP: F partials
    out = jax.lax.all_gather(out, da, axis=1, tiled=True)     # (T, D)
    if dp is not None:
        didx = jax.lax.axis_index(da)
        out = jax.lax.dynamic_slice(out, (didx * Tl, 0), (Tl, D))
    aux = jax.lax.pmean(aux, ma)
    return out.astype(x.dtype), aux


def moe_apply(x, params, *, cfg: ModelConfig, dist, decode: bool = False):
    """x: (B, S, D). params: wr (D, E), wi/wg (E, D, F), wo (E, F, D).

    dist: repro.sharding.DistContext (or None for the single-device ref)."""
    B, S, D = x.shape
    if dist is None or dist.mesh is None:
        y, aux = moe_ref(x.reshape(-1, D), params["wr"], params["wi"],
                         params["wg"], params["wo"], cfg.experts_per_token)
        return y.reshape(B, S, D), aux

    from jax.sharding import PartitionSpec as P
    ep = (cfg.num_experts % dist.model_size == 0)
    # batch mapped over dp only when divisible (B=1 long-context decode
    # replicates tokens across dp shards — latency-bound regime)
    dp = dist.dp_axes if B % max(dist.dp_size, 1) == 0 else None
    ma, da = dist.model_axis, dist.data_axis

    if decode:
        # weights stay sharded exactly as stored: (E|E_m, D/data, F|F_m)
        wspec = P(ma, da, None) if ep else P(None, da, ma)
        wo_spec = P(ma, None, da) if ep else P(None, ma, da)

        def body_d(xl, wr, wi, wg, wo):
            Tl = xl.shape[0] * xl.shape[1]
            y, aux = _decode_moe_sharded(xl.reshape(Tl, -1), wr, wi, wg, wo,
                                         cfg=cfg, ep=ep, dist=dist, dp=dp)
            return y.reshape(xl.shape), jnp.reshape(aux, (1,))

        y, aux = jax.shard_map(
            body_d, mesh=dist.mesh,
            in_specs=(P(dp, None, None), P(None, None), wspec, wspec,
                      wo_spec),
            out_specs=(P(dp, None, None), P(dp)),
            check_vma=False,
        )(x, params["wr"], params["wi"], params["wg"], params["wo"])
        return y, aux.mean()

    wspec = P(ma, None, None) if ep else P(None, None, ma)
    wo_spec = P(ma, None, None) if ep else P(None, ma, None)

    def body(xl, wr, wi, wg, wo):
        Tl = xl.shape[0] * xl.shape[1]
        y, aux = _local_moe(xl.reshape(Tl, -1), wr, wi, wg, wo, cfg=cfg,
                            expert_parallel=ep, model_axis=ma, decode=False)
        return y.reshape(xl.shape), jnp.reshape(aux, (1,))

    y, aux = jax.shard_map(
        body, mesh=dist.mesh,
        in_specs=(P(dp, None, None), P(None, None), wspec, wspec, wo_spec),
        out_specs=(P(dp, None, None), P(dp)),
        check_vma=False,
    )(x, params["wr"], params["wi"], params["wg"], params["wo"])
    return y, aux.mean()
