"""Unified model zoo: dense / MoE / SSM / hybrid / enc-dec / VLM stacks.

The layer stack is a ``lax.scan`` over *superblocks* — one period of
``cfg.block_pattern`` per step — which keeps HLO size O(1) in depth (critical
for 512-device AOT compiles).  Heterogeneous stacks (gemma2 local/global
alternation, jamba 1:7 mamba:attn, vision cross-attn interleave) unroll their
pattern *within* the superblock body.

Params are plain nested dicts; block params are stacked along a leading
superblock axis (via vmapped init) so the scan can slice one step at a time.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.layers import attention as attn
from repro.layers import mamba as mamba_l
from repro.layers import rwkv as rwkv_l
from repro.layers.common import (
    dense_init, embed_tokens, mlp_fwd, mlp_init, rmsnorm, rmsnorm_init,
    split_keys, unembed,
)
from repro.layers.moe import MeshContext, moe_fwd, moe_init

Shard = Callable[[str, jax.Array], jax.Array]
_id_shard: Shard = lambda name, x: x


# =========================================================== initialization
def _slot_init(key, cfg: ModelConfig, kind: str, slot: int, decoder: bool):
    ks = split_keys(key, 4)
    p = {"norm1": rmsnorm_init(cfg.d_model, cfg.pdtype)}
    if kind in ("attn", "attn_local"):
        p["mixer"] = attn.attention_init(ks[0], cfg)
    elif kind == "xattn":
        p["mixer"] = attn.attention_init(ks[0], cfg, cross=True)
        p["xgate"] = jnp.zeros((), jnp.float32)
    elif kind == "mamba":
        p["mixer"] = mamba_l.mamba_init(ks[0], cfg)
    elif kind == "rwkv6":
        p["mixer"] = rwkv_l.rwkv_time_init(ks[0], cfg)
    else:
        raise ValueError(kind)

    if decoder and cfg.n_encoder_layers and kind != "xattn":
        # enc-dec decoder: every block also cross-attends to the encoder
        p["norm_x"] = rmsnorm_init(cfg.d_model, cfg.pdtype)
        p["xattn"] = attn.attention_init(ks[2], cfg, cross=True)

    p["norm2"] = rmsnorm_init(cfg.d_model, cfg.pdtype)
    if kind == "rwkv6":
        p["mlp"] = rwkv_l.rwkv_channel_init(ks[1], cfg)
    elif slot in cfg.moe_slots and cfg.moe is not None:
        p["moe"] = moe_init(ks[1], cfg)
    else:
        p["mlp"] = mlp_init(ks[1], cfg)
    return p


def _superblock_init(key, cfg: ModelConfig, decoder: bool = True):
    ks = split_keys(key, len(cfg.block_pattern))
    return {f"slot{i}": _slot_init(ks[i], cfg, kind, i, decoder)
            for i, kind in enumerate(cfg.block_pattern)}


def _stack_init(key, cfg: ModelConfig, n: int, decoder: bool = True):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _superblock_init(k, cfg, decoder))(keys)


def init_params(key, cfg: ModelConfig):
    ks = split_keys(key, 5)
    p = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), cfg.pdtype),
        "blocks": _stack_init(ks[1], cfg, cfg.n_superblocks),
        "final_norm": rmsnorm_init(cfg.d_model, cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[2], (cfg.d_model, cfg.vocab_size), cfg.pdtype)
    if cfg.n_encoder_layers:
        enc_cfg = cfg.replace(block_pattern=("attn",), moe_slots=())
        n_enc = cfg.n_encoder_layers
        p["enc_blocks"] = _stack_init(ks[3], enc_cfg, n_enc, decoder=False)
        p["enc_norm"] = rmsnorm_init(cfg.d_model, cfg.pdtype)
    return p


# =========================================================== cache
def _slot_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                src_len: int, dtype, decoder: bool):
    c = {}
    if kind in ("attn", "attn_local"):
        c["self"] = attn.make_self_cache(cfg, batch, max_len, dtype)
    elif kind == "xattn":
        c["cross"] = attn.make_self_cache(cfg, batch, src_len, dtype)
    elif kind == "mamba":
        d_inner = cfg.mamba.expand * cfg.d_model
        c["mamba"] = {
            "conv": jnp.zeros((batch, cfg.mamba.d_conv - 1, d_inner), dtype),
            "h": jnp.zeros((batch, d_inner, cfg.mamba.d_state), jnp.float32)}
    elif kind == "rwkv6":
        c["rwkv"] = {
            "shift": jnp.zeros((batch, cfg.d_model), dtype),
            "s": jnp.zeros((batch, cfg.n_heads, cfg.hd, cfg.hd), jnp.float32),
            "shift_c": jnp.zeros((batch, cfg.d_model), dtype)}
    if decoder and cfg.n_encoder_layers and kind != "xattn":
        c["cross"] = attn.make_self_cache(cfg, batch, src_len, dtype)
    return c


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               src_len: int = 0, dtype=None):
    """Stacked (over superblocks) decode cache pytree."""
    dtype = dtype or cfg.cdtype
    per_sb = {f"slot{i}": _slot_cache(cfg, kind, batch, max_len, src_len,
                                      dtype, decoder=True)
              for i, kind in enumerate(cfg.block_pattern)}
    n = cfg.n_superblocks
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                        per_sb)


def make_paged_cache(cfg: ModelConfig, num_pages: int, block_size: int,
                     dtype=None, kv_dtype: str = "bf16", groups: int = 1):
    """Stacked (over superblocks) PAGED decode cache: per attention slot a
    pool of ``num_pages`` fixed-size token pages shared across batch rows
    through block tables (``forward(..., block_tables=...)``).  Only
    pure-attention stacks page — recurrent state (mamba/rwkv) is O(1) per
    slot and has nothing to page.

    Leaves are (n_superblocks, groups, P, row): one lane-dense row per
    layer, head group and page (``attn.make_paged_self_cache``).
    ``forward`` carries the pool whole through the layer loop and each
    layer reads and writes its own rows in place.  ``groups`` is 1 unless
    tensor parallelism shards the heads (``ShardedBackend``).

    ``kv_dtype="int8"`` swaps each slot's pages for the quantized layout
    (int8 payload + per-(token, head) f32 scale rows); ``forward``
    dispatches on the ``k_scale`` leaf, so callers thread the pytree
    through unchanged."""
    dtype = dtype or cfg.cdtype
    unsupported = [k for k in cfg.block_pattern
                   if k not in ("attn", "attn_local")]
    if unsupported:
        raise ValueError(
            f"paged KV cache supports pure-attention stacks only; "
            f"{cfg.name} has block kinds {unsupported}")
    if cfg.n_encoder_layers:
        raise ValueError("paged KV cache does not support enc-dec models")
    per_sb = {f"slot{i}": {"self": attn.make_paged_self_cache(
                  cfg, num_pages, block_size, dtype,
                  quantized=(kv_dtype == "int8"), groups=groups)}
              for i, kind in enumerate(cfg.block_pattern)}
    n = cfg.n_superblocks
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                        per_sb)


# =========================================================== forward
# jax.named_scope tag per mixer kind: these names land in each traced
# eqn's source_info.name_stack, which core.tracing copies onto
# Kernel.operator — the provenance the telemetry attribution layer keys on
_MIXER_SCOPE = {"attn": "attn", "attn_local": "attn", "xattn": "xattn",
                "mamba": "mamba", "rwkv6": "rwkv"}


def _apply_slot(bp, x, cfg: ModelConfig, kind: str, slot: int, *,
                positions, causal, cache, cache_index, encoder_out,
                dist, shd, aux, lengths=None, block_tables=None,
                reduce=None, layer=None):
    with jax.named_scope("norm1"):
        h = rmsnorm(x, bp["norm1"]["scale"], cfg.norm_eps)
    new_cache = dict(cache) if cache is not None else None

    with jax.named_scope(_MIXER_SCOPE.get(kind, kind)):
        if kind in ("attn", "attn_local"):
            window = cfg.sliding_window if kind == "attn_local" else 0
            o, nc = attn.attention_fwd(
                bp["mixer"], h, cfg, positions=positions, causal=causal,
                window=window,
                cache=None if cache is None else cache.get("self"),
                cache_index=cache_index, lengths=lengths,
                block_tables=block_tables,
                shd=None if shd is _id_shard else shd, reduce=reduce,
                layer=layer)
            if nc is not None:
                new_cache["self"] = nc
        elif kind == "xattn":
            o, nc = attn.attention_fwd(
                bp["mixer"], h, cfg, positions=positions, is_cross=True,
                cross_kv=encoder_out,
                cache=None if cache is None else cache.get("cross"),
                cache_index=cache_index)
            if nc is not None:
                new_cache["cross"] = nc
            o = o * jnp.tanh(bp["xgate"]).astype(o.dtype)
        elif kind == "mamba":
            o, nc = mamba_l.mamba_fwd(
                bp["mixer"], h, cfg,
                state=None if cache is None else cache.get("mamba"))
            if cache is not None:
                new_cache["mamba"] = nc
        elif kind == "rwkv6":
            st = None if cache is None else \
                {"shift": cache["rwkv"]["shift"], "s": cache["rwkv"]["s"]}
            o, nst = rwkv_l.rwkv_time_fwd(bp["mixer"], h, cfg, state=st,
                                          shd=shd)
            if cache is not None:
                new_cache["rwkv"] = dict(cache["rwkv"], **nst)
        else:
            raise ValueError(kind)
    with jax.named_scope("resid"):
        x = x + shd("resid", checkpoint_name(o, "block_out"))

    # enc-dec cross attention (seamless decoder)
    if "xattn" in bp and kind != "xattn":
        with jax.named_scope("xattn"):
            h = rmsnorm(x, bp["norm_x"]["scale"], cfg.norm_eps)
            o, nc = attn.attention_fwd(
                bp["xattn"], h, cfg, positions=positions, is_cross=True,
                cross_kv=encoder_out,
                cache=None if cache is None else cache.get("cross"),
                cache_index=cache_index)
            if nc is not None:
                new_cache["cross"] = nc
            x = x + shd("resid", o)

    with jax.named_scope("norm2"):
        h = rmsnorm(x, bp["norm2"]["scale"], cfg.norm_eps)
    if kind == "rwkv6":
        with jax.named_scope("rwkv_channel"):
            st = None if cache is None else {"shift": cache["rwkv"]["shift_c"]}
            o, nst = rwkv_l.rwkv_channel_fwd(bp["mlp"], h, cfg, state=st)
            if cache is not None:
                new_cache["rwkv"]["shift_c"] = nst["shift"]
    elif "moe" in bp:
        with jax.named_scope("moe"):
            o, a = moe_fwd(bp["moe"], h, cfg, dist=dist)
            o = checkpoint_name(o, "block_out")
            aux = aux + a
    else:
        with jax.named_scope("mlp"):
            o = mlp_fwd(bp["mlp"], h, cfg, reduce=reduce)
    with jax.named_scope("resid"):
        x = x + shd("resid", o)
    return x, new_cache, aux


REMAT_POLICIES = {
    "nothing": None,   # jax.checkpoint default: save nothing, recompute all
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
    # "names": save tagged block outputs — backward skips re-running the
    # mixers/MoE (and their FSDP weight gathers / dispatch all_to_alls) at
    # the cost of one extra (B,S,D) per sub-block
    "names": "names",
}


def _run_stack(blocks, x, cfg: ModelConfig, pattern, *, positions, causal,
               cache, cache_index, encoder_out, dist, shd, remat: bool,
               remat_policy: str = "nothing", unroll: bool = False,
               lengths=None, block_tables=None, reduce=None):
    def step(x, aux, bp, cache_sb, layer):
        new_cache_sb = {}
        for i, kind in enumerate(pattern):
            sl = f"slot{i}"
            with jax.named_scope(sl):
                x, nc, aux = _apply_slot(
                    bp[sl], x, cfg, kind, i, positions=positions,
                    causal=causal,
                    cache=None if cache_sb is None else cache_sb[sl],
                    cache_index=cache_index, encoder_out=encoder_out,
                    dist=dist, shd=shd, aux=aux, lengths=lengths,
                    block_tables=block_tables, reduce=reduce, layer=layer)
            new_cache_sb[sl] = nc if nc is not None else {}
        return shd("resid", x), aux, new_cache_sb

    # A paged pool rides whole in the carry and each layer reads and
    # writes its own rows in place (xs: the layer index).  Scanning it as
    # xs/ys would slice and restack one layer of every leaf per step, and
    # on the TPU each slice also changes layout: four pool-layer copies
    # per leaf per layer.  Other caches are per-layer xs/ys.
    pooled = cache is not None and any(
        "k_pages" in c.get("self", {}) for c in cache.values())

    def body(carry, xs):
        if pooled:
            x, aux, pool = carry
            bp, layer = xs
            return step(x, aux, bp, pool, layer), None
        x, aux = carry
        bp, cache_sb = xs
        x, aux, new_cache_sb = step(x, aux, bp, cache_sb, None)
        return (x, aux), new_cache_sb

    if remat:
        pol = REMAT_POLICIES.get(remat_policy, None)
        if pol == "names":
            kw = {"policy": jax.checkpoint_policies.save_only_these_names(
                "block_out")}
        elif pol:
            kw = {"policy": getattr(jax.checkpoint_policies, pol)}
        else:
            kw = {}
        body = jax.checkpoint(body, **kw)
    n = jax.tree.leaves(blocks)[0].shape[0]
    carry = (x, jnp.zeros((), jnp.float32)) + ((cache,) if pooled else ())
    xs = (blocks, np.arange(n, dtype=np.int32) if pooled else cache)
    if unroll:
        # python loop: per-layer kernel streams stay visible to the SKIP
        # profiler (and to XLA's scheduler for overlap experiments); a
        # pool's layer index is a static int here
        ys = []
        for i in range(n):
            with jax.named_scope(f"layer{i}"):
                carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        new_cache = jax.tree.map(lambda *cs: jnp.stack(cs), *ys) \
            if jax.tree.leaves(ys[0]) else ys[0]
    else:
        carry, new_cache = jax.lax.scan(body, carry, xs)
    if pooled:
        return carry
    x, aux = carry
    return x, aux, new_cache


def forward(params, tokens, cfg: ModelConfig, *,
            positions: Optional[jax.Array] = None,
            cache=None, cache_index=None,
            encoder_tokens=None,          # enc-dec: (B,S_enc,D) frame embeds
            frontend_embeds=None,         # vlm: (B,T_img,D) patch embeds
            dist: Optional[MeshContext] = None,
            shd: Shard = _id_shard,
            remat: bool = False,
            remat_policy: str = "nothing",
            return_hidden: bool = False,
            unroll: bool = False,
            lengths: Optional[jax.Array] = None,
            block_tables: Optional[jax.Array] = None,
            reduce=None):
    """Returns (logits_f32, aux, new_cache) — or final hidden states instead
    of logits when return_hidden (chunked-loss path skips the unembed).
    unroll=True runs the layer stack as a python loop (SKIP profiling).
    lengths: (B,) per-row positions for continuous-batching decode.
    block_tables: (B,NB) page ids when ``cache`` is paged (make_paged_cache);
    shared by every layer — the table redirects where pages live, and the
    same block layout is used across the stack.
    reduce: tensor-parallel output hook ``(name, x) -> x`` applied to the
    partial-sum attention/MLP outputs — psum inside a shard_map body when
    params are Megatron-sharded over a model axis (cfg then carries LOCAL
    head counts); None everywhere else."""
    b, s = tokens.shape
    if cache_index is None:
        cache_index = jnp.zeros((), jnp.int32)
    if positions is None:
        if lengths is not None:
            positions = (lengths[:, None].astype(jnp.int32)
                         + jnp.arange(s, dtype=jnp.int32))
        else:
            positions = cache_index + jnp.arange(s, dtype=jnp.int32)
            positions = jnp.broadcast_to(positions[None], (b, s))
    causal = cfg.family != "encoder"

    encoder_out = None
    if cfg.n_encoder_layers and encoder_tokens is not None:
        enc_cfg = cfg.replace(block_pattern=("attn",), moe_slots=())
        enc_x = shd("act", encoder_tokens.astype(cfg.cdtype))
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc_x.shape[1], dtype=jnp.int32)[None],
            enc_x.shape[:2])
        enc_x, _, _ = _run_stack(
            params["enc_blocks"], enc_x, enc_cfg, ("attn",),
            positions=enc_pos, causal=False, cache=None, cache_index=None,
            encoder_out=None, dist=dist, shd=shd, remat=remat,
            remat_policy=remat_policy, unroll=unroll)
        encoder_out = rmsnorm(enc_x, params["enc_norm"]["scale"], cfg.norm_eps)
    elif frontend_embeds is not None:
        encoder_out = frontend_embeds.astype(cfg.cdtype)

    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tokens, cfg).astype(cfg.cdtype)
        x = shd("act", x)
    x, aux, new_cache = _run_stack(
        params["blocks"], x, cfg, cfg.block_pattern,
        positions=positions, causal=causal, cache=cache,
        cache_index=cache_index, encoder_out=encoder_out,
        dist=dist, shd=shd, remat=remat, remat_policy=remat_policy,
        unroll=unroll, lengths=lengths, block_tables=block_tables,
        reduce=reduce)
    with jax.named_scope("final_norm"):
        x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if return_hidden:
        return x, aux, (new_cache if cache is not None else None)
    with jax.named_scope("unembed"):
        logits = unembed(x, params["embed"], params.get("lm_head"), cfg)
        logits = shd("logits", logits)
    return logits, aux, (new_cache if cache is not None else None)


# =========================================================== loss
def loss_fn(params, batch, cfg: ModelConfig, *, dist=None, shd=_id_shard,
            remat: bool = True, remat_policy: str = "nothing",
            aux_weight: float = 0.01, loss_chunks: int = 1):
    """Next-token CE.  batch: {"tokens","labels", optional encoder inputs}.

    loss_chunks > 1 computes the CE over sequence chunks inside a scan so the
    full (B,S,V) logits tensor is never materialized (vocab-heavy archs).
    """
    labels = batch["labels"]
    if loss_chunks > 1:
        hidden, aux, _ = forward(
            params, batch["tokens"], cfg,
            encoder_tokens=batch.get("encoder_tokens"),
            frontend_embeds=batch.get("frontend_embeds"),
            dist=dist, shd=shd, remat=remat, remat_policy=remat_policy,
            return_hidden=True)
        ce = _chunked_ce(hidden, labels, params, cfg, loss_chunks, shd)
    else:
        logits, aux, _ = forward(
            params, batch["tokens"], cfg,
            encoder_tokens=batch.get("encoder_tokens"),
            frontend_embeds=batch.get("frontend_embeds"),
            dist=dist, shd=shd, remat=remat, remat_policy=remat_policy)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        ce = jnp.mean(logz - gold)
    return ce + aux_weight * aux, (ce, aux)


def _chunked_ce(hidden, labels, params, cfg: ModelConfig, n_chunks: int, shd):
    """CE over sequence chunks: the (B,S,V) logits tensor never materializes;
    jax.checkpoint recomputes each chunk's logits in backward."""
    b, s, d = hidden.shape
    assert s % n_chunks == 0, (s, n_chunks)
    sc = s // n_chunks
    xs = hidden.reshape(b, n_chunks, sc, d).swapaxes(0, 1)
    ls = labels.reshape(b, n_chunks, sc).swapaxes(0, 1)

    def chunk(carry, xl):
        xc, lc = xl
        logits = unembed(xc, params["embed"], params.get("lm_head"), cfg)
        logits = shd("logits", logits)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros((), jnp.float32),
                            (xs, ls))
    return total / (b * s)


# =========================================================== stats
def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def active_param_count(params, cfg: ModelConfig) -> int:
    """Params touched per token (MoE: top_k of n_experts), excl. embeddings."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "embed" in keys or "lm_head" in keys:
            continue
        n = leaf.size
        if cfg.moe and any(k in ("w_in", "w_gate", "w_out") for k in keys) \
                and "moe" in keys:
            n = n * cfg.moe.top_k // cfg.moe.n_experts
        total += n
    return total
