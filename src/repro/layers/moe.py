"""Mixture-of-Experts: router, capacity dispatch, expert-parallel execution.

Three execution paths sharing the same math:

* ``moe_dense_fwd``   — naive all-experts reference (tiny tests only).
* ``moe_local_fwd``   — sort-based capacity dispatch, all experts local
                        (single-device smoke tests; also the per-shard body
                        of the EP paths).
* ``moe_ep_fwd``      — expert parallelism over the ``model`` mesh axis via
                        shard_map.  Two modes:
                          - "seq": tokens sequence-sharded over the EP axis,
                            all_to_all dispatch/return (train & prefill).
                          - "rep": tokens replicated over the EP axis, each
                            shard computes only its local experts, psum
                            combine (decode, where seq is unshardable).

Capacity dropping: per-shard capacity C = ceil(T*k/E * capacity_factor)
rounded up to a multiple of 8; tokens beyond capacity are dropped (standard
Switch-style semantics).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.layers.common import activation, dense_init, split_keys


class MeshContext(NamedTuple):
    """Distribution context threaded through model forwards."""
    mesh: object                   # jax.sharding.Mesh
    dp_axes: Tuple[str, ...]       # batch-sharding axes, e.g. ("pod","data")
    tp_axis: str                   # tensor/expert-parallel axis, e.g. "model"
    fsdp_axis: Optional[str] = None  # ZeRO-3 axis for expert weights (kimi/jamba)

    @property
    def ep_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def fsdp_size(self) -> int:
        return self.mesh.shape[self.fsdp_axis] if self.fsdp_axis else 1


# ------------------------------------------------------------------ init
def moe_init(key, cfg: ModelConfig):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.n_experts
    ks = split_keys(key, 6)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32, scale=0.006),
        "w_in": dense_init(ks[1], (e, d, f), cfg.pdtype),
        "w_gate": dense_init(ks[2], (e, d, f), cfg.pdtype),
        "w_out": dense_init(ks[3], (e, f, d), cfg.pdtype),
    }
    if m.n_shared_experts:
        fs = f * m.n_shared_experts
        p["shared_in"] = dense_init(ks[4], (d, fs), cfg.pdtype)
        p["shared_gate"] = dense_init(ks[5], (d, fs), cfg.pdtype)
        p["shared_out"] = dense_init(ks[4], (fs, d), cfg.pdtype)
    return p


# ------------------------------------------------------------------ router
def route(x2d, router_w, cfg: ModelConfig):
    """x2d: (T, D) -> gates (T,k) f32, eids (T,k) i32, aux-loss scalar."""
    m = cfg.moe
    logits = x2d.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)           # renorm
    # load-balancing aux (Switch): E * sum_e f_e * P_e
    onehot = jax.nn.one_hot(eids, m.n_experts, dtype=jnp.float32)    # (T,k,E)
    f_e = jnp.mean(jnp.sum(onehot, axis=1), axis=0)                  # (E,)
    p_e = jnp.mean(probs, axis=0)                                    # (E,)
    aux = m.n_experts * jnp.sum(f_e * p_e) / m.top_k
    return gates, eids.astype(jnp.int32), aux


def capacity(t_local: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(-(-t_local * m.top_k * m.capacity_factor // m.n_experts))
    return max(8, -(-c // 8) * 8)


def dispatch_slots(eids, n_experts: int, cap: int):
    """Sort-based position-in-expert.  eids: (T,k) -> slots (T*k,), keep (T*k,).

    slot = expert_id * cap + position_within_expert for kept assignments;
    dropped assignments get slot = n_experts*cap (a dump row).
    """
    tk = eids.size
    flat_e = eids.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(n_experts), side="left")
    pos_sorted = jnp.arange(tk, dtype=jnp.int32) - seg_start[sorted_e].astype(jnp.int32)
    pos = jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, n_experts * cap)
    return slot, keep


def expert_ffn(w_in, w_gate, w_out, xb, act: str):
    """xb: (E, N, D) batched per-expert FFN."""
    h = jnp.einsum("end,edf->enf", xb, w_in)
    g = jnp.einsum("end,edf->enf", xb, w_gate)
    h = activation(g, act) * h
    return jnp.einsum("enf,efd->end", h, w_out)


def _shared(params, x2d, cfg: ModelConfig):
    if "shared_in" not in params:
        return 0.0
    h = x2d @ params["shared_in"]
    g = activation(x2d @ params["shared_gate"], cfg.act)
    return (g * h) @ params["shared_out"]


# ------------------------------------------------------------------ dense ref
def moe_dense_fwd(params, x, cfg: ModelConfig):
    """All experts on all tokens — O(E) flops, tiny-test reference only."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    gates, eids, aux = route(xt, params["router"], cfg)
    xb = jnp.broadcast_to(xt[None], (cfg.moe.n_experts,) + xt.shape)
    ys = expert_ffn(params["w_in"], params["w_gate"], params["w_out"], xb, cfg.act)
    # combine: sum_k gate_k * y[eid_k]
    yk = jnp.take_along_axis(
        ys.transpose(1, 0, 2), eids[..., None].astype(jnp.int32), axis=1)  # (T,k,D)
    out = jnp.sum(gates[..., None].astype(yk.dtype) * yk, axis=1)
    out = out + _shared(params, xt, cfg)
    return out.reshape(b, s, d).astype(x.dtype), aux


# ------------------------------------------------------------------ local
def _dispatch_combine(params, xt, cfg: ModelConfig, w_in, w_gate, w_out,
                      expert_mask=None, local_offset=None):
    """Shared body: route/dispatch xt (T,D) against given expert weights.

    expert_mask: optional (E,) bool — only dispatch to these experts (rep-EP).
    local_offset: first expert id owned by this shard (rep-EP).
    Returns (combined (T,D), aux).
    """
    t, d = xt.shape
    e_global = cfg.moe.n_experts
    cap = capacity(t, cfg)
    gates, eids, aux = route(xt, params["router"], cfg)
    slot, keep = dispatch_slots(eids, e_global, cap)
    if expert_mask is not None:
        keep = keep & expert_mask[eids.reshape(-1)]
        slot = jnp.where(keep, slot, e_global * cap)
    # gather token vectors per assignment and scatter into the expert buffer
    tok_idx = jnp.arange(t * cfg.moe.top_k, dtype=jnp.int32) // cfg.moe.top_k
    buf = jnp.zeros((e_global * cap + 1, d), xt.dtype)
    buf = buf.at[slot].set(xt[tok_idx], mode="drop")
    buf = buf[:-1].reshape(e_global, cap, d)

    e_local = w_in.shape[0]
    if e_local != e_global:
        # rep-EP: this shard owns experts [lo, lo+e_local); slice its rows
        lo = local_offset
        buf = jax.lax.dynamic_slice_in_dim(buf, lo, e_local, axis=0)
    ys = expert_ffn(w_in, w_gate, w_out, buf, cfg.act)                # (El,C,D)
    if e_local != e_global:
        full = jnp.zeros((e_global, cap, d), ys.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(full, ys, lo, axis=0)
        ys = full
    # combine
    ys_flat = jnp.concatenate(
        [ys.reshape(e_global * cap, d), jnp.zeros((1, d), ys.dtype)], axis=0)
    yk = ys_flat[slot].reshape(t, cfg.moe.top_k, d)
    gk = jnp.where(keep.reshape(t, cfg.moe.top_k), gates, 0.0)
    out = jnp.sum(gk[..., None].astype(jnp.float32) * yk.astype(jnp.float32), axis=1)
    return out.astype(xt.dtype), aux


def moe_local_fwd(params, x, cfg: ModelConfig):
    """Single-device capacity-dispatch MoE (no collectives)."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    out, aux = _dispatch_combine(params, xt, cfg, params["w_in"],
                                 params["w_gate"], params["w_out"])
    out = out + _shared(params, xt, cfg)
    return out.reshape(b, s, d).astype(x.dtype), aux


# ------------------------------------------------------------------ EP
def _gather_experts(params, fsdp_axis):
    """ZeRO-3: expert weights arrive d_ff-sharded over fsdp_axis; all-gather
    them just-in-time (storage stays sharded, compute sees full experts)."""
    if not fsdp_axis:
        return params
    p = dict(params)
    p["w_in"] = jax.lax.all_gather(params["w_in"], fsdp_axis, axis=2,
                                   tiled=True)
    p["w_gate"] = jax.lax.all_gather(params["w_gate"], fsdp_axis, axis=2,
                                     tiled=True)
    p["w_out"] = jax.lax.all_gather(params["w_out"], fsdp_axis, axis=1,
                                    tiled=True)
    return p


def _ep_seq_body(params, x, cfg: ModelConfig, dp_axes, tp_axis,
                 fsdp_axis=None):
    """Per-shard body, tokens seq-sharded over tp_axis.  x: (Bl, Sl, D)."""
    params = _gather_experts(params, fsdp_axis)
    bl, sl, d = x.shape
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    m = cfg.moe
    e_global = m.n_experts
    e_local = e_global // jax.lax.axis_size(tp_axis)
    cap = capacity(t, cfg)
    gates, eids, aux = route(xt, params["router"], cfg)
    slot, keep = dispatch_slots(eids, e_global, cap)
    tok_idx = jnp.arange(t * m.top_k, dtype=jnp.int32) // m.top_k
    buf = jnp.zeros((e_global * cap + 1, d), xt.dtype)
    buf = buf.at[slot].set(xt[tok_idx], mode="drop")[:-1]
    buf = buf.reshape(e_global, cap, d)
    # exchange: (E, C, D) -> rows regrouped so this shard holds its experts'
    # tokens from every peer: (ep*E_local, C, D) with blocks [peer, local_e]
    buf = jax.lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=0,
                             tiled=True)
    ep = jax.lax.axis_size(tp_axis)
    xb = buf.reshape(ep, e_local, cap, d).transpose(1, 0, 2, 3)
    xb = xb.reshape(e_local, ep * cap, d)
    ys = expert_ffn(params["w_in"], params["w_gate"], params["w_out"], xb, cfg.act)
    ys = ys.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3)
    ys = ys.reshape(e_global, cap, d)
    ys = jax.lax.all_to_all(ys, tp_axis, split_axis=0, concat_axis=0,
                            tiled=True)
    ys_flat = jnp.concatenate(
        [ys.reshape(e_global * cap, d), jnp.zeros((1, d), ys.dtype)], axis=0)
    yk = ys_flat[slot].reshape(t, m.top_k, d)
    gk = jnp.where(keep.reshape(t, m.top_k), gates, 0.0)
    out = jnp.sum(gk[..., None].astype(jnp.float32) * yk.astype(jnp.float32),
                  axis=1).astype(xt.dtype)
    out = out + _shared(params, xt, cfg)
    aux = jax.lax.pmean(aux, dp_axes + (tp_axis,)) if dp_axes else \
        jax.lax.pmean(aux, tp_axis)
    return out.reshape(bl, sl, d), aux


def _ep_rep_body(params, x, cfg: ModelConfig, dp_axes, tp_axis,
                 fsdp_axis=None):
    """Per-shard body, tokens replicated over tp_axis.  x: (Bl, S, D)."""
    params = _gather_experts(params, fsdp_axis)
    bl, s, d = x.shape
    xt = x.reshape(-1, d)
    ep = jax.lax.axis_size(tp_axis)
    e_local = cfg.moe.n_experts // ep
    my = jax.lax.axis_index(tp_axis)
    expert_mask = (jnp.arange(cfg.moe.n_experts) // e_local) == my
    out, aux = _dispatch_combine(
        params, xt, cfg,
        params["w_in"], params["w_gate"], params["w_out"],
        expert_mask=expert_mask, local_offset=my * e_local)
    out = jax.lax.psum(out, tp_axis)
    # shared experts once (identical on every shard — do NOT psum)
    out = out + _shared(params, xt, cfg)
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)
    return out.reshape(bl, s, d), aux


def moe_ep_fwd(params, x, cfg: ModelConfig, dist: MeshContext,
               mode: str = "auto"):
    """Expert-parallel MoE.  x: (B, S, D) global."""
    if mode == "auto":
        mode = "seq" if x.shape[1] % dist.ep_size == 0 else "rep"
    tp = dist.tp_axis
    # effective dp axes: longest prefix whose product divides the batch
    # (decode at batch=1 runs fully replicated over dp)
    dp, prod = [], 1
    for a in dist.dp_axes:
        if x.shape[0] % (prod * dist.mesh.shape[a]) == 0:
            dp.append(a)
            prod *= dist.mesh.shape[a]
    dp = tuple(dp)
    fsdp = dist.fsdp_axis
    if fsdp and (cfg.moe.d_expert % dist.fsdp_size or
                 cfg.moe.n_experts % dist.ep_size):
        fsdp = None
    wspec = {"router": P(),
             "w_in": P(tp, None, fsdp),
             "w_gate": P(tp, None, fsdp),
             "w_out": P(tp, fsdp, None)}
    for k in ("shared_in", "shared_gate", "shared_out"):
        if k in params:
            wspec[k] = P()
    wspec = {k: wspec[k] for k in params}
    if mode == "seq":
        body = functools.partial(_ep_seq_body, cfg=cfg, dp_axes=dp,
                                 tp_axis=tp, fsdp_axis=fsdp)
        xspec = P(dp, tp, None)
    else:
        body = functools.partial(_ep_rep_body, cfg=cfg, dp_axes=dp,
                                 tp_axis=tp, fsdp_axis=fsdp)
        xspec = P(dp, None, None)
    fn = jax.shard_map(
        lambda p_, x_: body(p_, x_),
        mesh=dist.mesh,
        in_specs=(wspec, xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )
    return fn(params, x)


def moe_fwd(params, x, cfg: ModelConfig, dist: Optional[MeshContext] = None,
            mode: str = "auto"):
    """Entry point: EP when a mesh context is given, local otherwise."""
    if dist is None:
        return moe_local_fwd(params, x, cfg)
    return moe_ep_fwd(params, x, cfg, dist, mode=mode)
