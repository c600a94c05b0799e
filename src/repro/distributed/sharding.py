"""Sharding-rule engine: param/cache/activation PartitionSpecs with
divisibility-aware fallback.

Rules map pytree leaf paths to *candidate* specs; any axis that does not
divide the corresponding dimension is dropped (replicated) — this is what
makes e.g. smollm's 15-head attention or 8-KV-head caches lower cleanly on a
16-way model axis without special cases.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


# ------------------------------------------------------------------ helpers
def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


def valid_spec(shape, spec: P, mesh: Mesh) -> P:
    """Drop spec axes that don't divide the dim (replicate instead)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, axis in zip(shape, entries):
        out.append(axis if axis and dim % _axis_size(mesh, axis) == 0 else None)
    return P(*out)


def shardings_for(tree, spec_tree, mesh: Mesh):
    return jax.tree.map(
        lambda x, s: NamedSharding(mesh, valid_spec(x.shape, s, mesh)),
        tree, spec_tree)


# ------------------------------------------------------------------ params
def _param_spec(path_keys, leaf_shape, cfg: ModelConfig, tp: str,
                stacked: bool, fsdp_experts: bool = False) -> P:
    """Candidate spec for one param leaf (before divisibility fallback)."""
    name = path_keys[-1]
    inblock = stacked  # stacked block params carry a leading superblock dim
    pre = (None,) if inblock else ()

    def mk(*dims):
        return P(*(pre + dims))

    # --- embeddings / head
    if name == "embed":
        return P(tp, None)
    if name == "lm_head":
        return P(None, tp)
    # --- norms and scalars
    if name in ("scale", "bias", "xgate", "w0", "u", "ln_scale", "ln_bias",
                "conv_b", "dt_b", "D"):
        return mk(*(None,) * len(leaf_shape[1 if inblock else 0:]))
    # --- MoE
    if "moe" in path_keys:
        if name == "router":
            return mk(None, None)
        if name in ("w_in", "w_gate"):         # (E, D, F)
            return mk(tp, None, "data" if fsdp_experts else None)
        if name == "w_out":                    # (E, F, D)
            return mk(tp, "data" if fsdp_experts else None, None)
        if name in ("shared_in", "shared_gate"):
            return mk(None, tp)
        if name == "shared_out":
            return mk(tp, None)
    # --- rwkv time/channel mix
    if name in ("mix_r", "mix_k", "mix_v", "mix_w", "mix_g"):
        return mk(None)
    if name in ("wA",):
        return mk(None, None)
    if name in ("wB",):
        return mk(None, None)
    # --- mamba
    if name == "in_proj":
        return mk(None, tp)
    if name == "conv_w":
        return mk(None, tp)
    if name == "x_proj":
        return mk(tp, None)
    if name == "dt_w":
        return mk(None, tp)
    if name == "A_log":
        return mk(tp, None)
    if name == "out_proj":
        return mk(tp, None)
    # --- rwkv channel-mix lives under "mlp": (D,F)/(F,D) like a dense MLP
    if "mlp" in path_keys and name == "wk":
        return mk(None, tp)
    if "mlp" in path_keys and name == "wv":
        return mk(tp, None)
    # --- attention & generic projections (head-aligned check done by caller)
    if name in ("wq", "wk", "wv", "wg", "wr"):
        return mk(None, tp)
    if name in ("bq", "bk", "bv"):
        return mk(tp)
    if name == "wo":
        return mk(tp, None)
    # --- dense mlp / rwkv channel
    if name in ("w_in", "w_gate", "wk"):
        return mk(None, tp)
    if name in ("w_out", "wv"):
        return mk(tp, None)
    return mk(*(None,) * len(leaf_shape[1 if inblock else 0:]))


def _head_aligned(name, path_keys, shape, cfg: ModelConfig, mesh, tp,
                  stacked) -> bool:
    """Attention projections: only shard the flattened head dim if the shard
    boundary falls between heads (H % tp == 0)."""
    if "moe" in path_keys or "mlp" in path_keys:
        return True
    if name in ("wq", "wo", "wg", "wr"):
        return cfg.n_heads % mesh.shape[tp] == 0
    if name in ("wk", "wv", "bk", "bv"):
        return cfg.n_kv_heads % mesh.shape[tp] == 0
    if name == "bq":
        return cfg.n_heads % mesh.shape[tp] == 0
    return True


def param_specs(params, cfg: ModelConfig, mesh: Mesh, tp: str = "model",
                fsdp_experts: bool = False):
    """PartitionSpec pytree for a param pytree.

    fsdp_experts: ZeRO-3 storage for MoE expert weights — d_ff additionally
    sharded over "data"; gathered just-in-time inside the MoE shard_map.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def key_of(p):
        return getattr(p, "key", getattr(p, "name", str(p)))

    specs = []
    for path, leaf in flat:
        keys = [key_of(p) for p in path]
        stacked = "blocks" in keys or "enc_blocks" in keys
        name = keys[-1]
        # rwkv wk/wv live under "mlp" (channel mix) or "mixer" (time mix)
        spec = _param_spec(keys, leaf.shape, cfg, tp, stacked, fsdp_experts)
        if not _head_aligned(name, keys, leaf.shape, cfg, mesh, tp, stacked):
            spec = P(*((None,) * len(leaf.shape)))
        spec = valid_spec(leaf.shape, spec, mesh)
        specs.append(spec)
    treedef = jax.tree.structure(params)
    return jax.tree.unflatten(treedef, specs)


# ------------------------------------------------------------------ cache
KV_REPLICATE_BUDGET = 4e9   # bytes/device a replicated-over-tp cache may use


def cache_specs(cache, cfg: ModelConfig, mesh: Mesh,
                dp=("data",), tp: str = "model"):
    """KV caches: batch over dp; heads over tp when divisible.  When heads
    don't divide: REPLICATE over tp if the per-device cache fits the budget
    (attention then needs NO collectives at decode); otherwise shard the
    sequence dim over tp (distributed online-softmax)."""
    dpt = tuple(dp)
    n_dp = math.prod(mesh.shape[a] for a in dpt)
    kv_total = sum(
        leaf.size * leaf.dtype.itemsize
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        if getattr(path[-1], "key", getattr(path[-1], "name", "")) in ("k", "v"))
    kv_fits = (kv_total / max(n_dp, 1)) <= KV_REPLICATE_BUDGET

    def spec_for(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = keys[-1]
        shape = leaf.shape
        if name in ("k", "v"):                 # (n_sb, B, T, HKV, hd)
            if cfg.n_kv_heads % mesh.shape[tp] == 0:
                s = P(None, dpt, None, tp, None)
            elif kv_fits:
                s = P(None, dpt, None, None, None)
            else:
                s = P(None, dpt, tp, None, None)
            return valid_spec(shape, s, mesh)
        if name == "h":                        # mamba (n_sb, B, di, ds)
            return valid_spec(shape, P(None, dpt, tp, None), mesh)
        if name == "conv":                     # (n_sb, B, K-1, di)
            return valid_spec(shape, P(None, dpt, None, tp), mesh)
        if name == "s":                        # rwkv (n_sb, B, H, hd, hd)
            return valid_spec(shape, P(None, dpt, None, None, None), mesh)
        if name in ("shift", "shift_c"):       # (n_sb, B, D)
            return valid_spec(shape, P(None, dpt, None), mesh)
        return valid_spec(shape, P(*(None,) * len(shape)), mesh)

    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    specs = [spec_for(p, leaf) for p, leaf in flat]
    return jax.tree.unflatten(jax.tree.structure(cache), specs)


def paged_cache_specs(pages, cfg: ModelConfig, mesh: Mesh,
                      tp: str = "model"):
    """Specs for the block-table paged pages pytree (``make_paged_cache``).

    Page pools are global (shared across batch rows through block tables),
    so there is no batch axis to put ``data`` on.  Every leaf —
    ``k_pages``/``v_pages`` (n_sb, G, P, *row) and, when the pool is
    quantized, ``k_scale``/``v_scale`` (n_sb, G, P, bs·(HKV/G)) — shards
    its head-group axis G over ``tp``: built with G = tp, each device holds
    whole heads, and inside ``shard_map`` its block is a valid pool of the
    per-device config.  A G that ``tp`` does not divide falls back to
    replication (``valid_spec``)."""
    def spec_for(path, leaf):
        name = getattr(path[-1], "key", getattr(path[-1], "name", ""))
        if name in ("k_pages", "v_pages", "k_scale", "v_scale"):
            return valid_spec(leaf.shape,
                              P(None, tp, *(None,) * (leaf.ndim - 2)), mesh)
        return valid_spec(leaf.shape, P(*(None,) * len(leaf.shape)), mesh)

    flat = jax.tree_util.tree_flatten_with_path(pages)[0]
    specs = [spec_for(p, leaf) for p, leaf in flat]
    return jax.tree.unflatten(jax.tree.structure(pages), specs)


# ------------------------------------------------------------------ activations
def make_shd(mesh: Mesh, dp=("data",), tp: str = "model",
             seq_shard: bool = False):
    """Activation-sharding hook passed into model forward.

    seq_shard=True puts the residual stream in Megatron-style sequence
    parallelism: (B, S, D) sharded (dp, tp, None).  GSPMD then all-gathers S
    before attention/MLP and reduce-scatters after — activation memory for
    remat-saved layer boundaries drops by the tp size.
    """
    dpt = tuple(dp)

    def shd(name: str, x):
        if name in ("act", "resid"):
            if seq_shard and x.ndim == 3:
                spec = P(dpt, tp, *((None,) * (x.ndim - 2)))
            else:
                spec = P(dpt, *((None,) * (x.ndim - 1)))
        elif name == "logits":
            spec = P(dpt, None, tp)
        elif name == "q_decode":
            spec = P(dpt, *((None,) * (x.ndim - 1)))
        elif name in ("q_heads", "kv_heads"):
            # attention runs HEAD-parallel: full sequence per device, heads
            # over tp (kv heads fall back to replicated when indivisible).
            # Without this GSPMD keeps attention context-parallel and the
            # backward all-reduces dK/dV per flash block (dominant wire
            # cost on MoE/GQA trains).
            spec = P(dpt, None, tp, None)
        elif name == "wkv":
            # batch-overshard across every divisible axis (recurrent mixers
            # with non-TP-shardable head counts)
            axes, prod = [], 1
            for a in dpt + (tp,):
                if x.shape[0] % (prod * mesh.shape[a]) == 0:
                    axes.append(a)
                    prod *= mesh.shape[a]
            spec = P(tuple(axes), *((None,) * (x.ndim - 1)))
        else:
            spec = P(*(None,) * x.ndim)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, valid_spec(x.shape, spec, mesh)))

    return shd
