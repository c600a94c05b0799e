"""Plain float32 reference of a dense decoder (Llama / Qwen2 family).

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * g1
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (bias if present)
                rotary embedding on q and k (rotate-half, theta from the config)
                grouped-query causal attention, scale 1/sqrt(head_dim)
                x = x + attn Wo
                h = rmsnorm(x) * g2
                x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = rmsnorm(x) * gf @ (embed^T if tied else lm_head)

Written from the published description of the architecture; it imports
nothing of the program under test.  Weights are drawn from the seed one
layer at a time (``harness.weights``), so a 7B model runs on one chip.
Matrix products run at ``highest`` precision, so float32 is float32 on a
TPU as well.

``mode="fp8"`` is the control: every matrix product takes its two inputs
rounded to float8 (e4m3, one scale per tensor), the step below bfloat16.
``mode="bf16"`` rounds them to bfloat16 instead, as the served program
does; it is a witness of what bfloat16 rounding alone does, not a check.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import _leaf_key, draw, layer_leaf

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
PAD = 256           # sequences are padded to a multiple of this


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    hq = model["num_attention_heads"]
    return {"d": d, "hq": hq, "hkv": model["num_key_value_heads"],
            "hd": model.get("head_dim", d // hq),
            "f": model["intermediate_size"], "V": model["vocab_size"],
            "L": model["num_hidden_layers"],
            "tied": model["tie_word_embeddings"],
            "bias": model.get("qkv_bias", False),
            "eps": model["rms_norm_eps"], "theta": model["rope_theta"],
            "dtype": jnp.dtype(model["torch_dtype"])}


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(a, b, low, eq: str = "...d,df->...f"):
    if low == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif low == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[..., None].astype(jnp.float32) * inv           # (B,T,hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer_paths(D: dict) -> dict:
    d, hq, hkv, hd, f = D["d"], D["hq"], D["hkv"], D["hd"], D["f"]
    p = {"norm1/scale": (d,), "mixer/wq": (d, hq * hd),
         "mixer/wk": (d, hkv * hd), "mixer/wv": (d, hkv * hd),
         "mixer/wo": (hq * hd, d), "norm2/scale": (d,),
         "mlp/w_in": (d, f), "mlp/w_gate": (d, f), "mlp/w_out": (f, d)}
    if D["bias"]:
        p.update({"mixer/bq": (hq * hd,), "mixer/bk": (hkv * hd,),
                  "mixer/bv": (hkv * hd,)})
    return p


@partial(jax.jit, static_argnums=(0,))
def _layer_weights(Dt, key, layer):
    D = dict(Dt)
    return {name: layer_leaf(key, f"blocks/slot0/{name}", (None,) + shape,
                             D["dtype"], layer).astype(jnp.float32)
            for name, shape in _layer_paths(D).items()}


@partial(jax.jit, static_argnums=(0, 1, 2))
def _global_weight(name, shape, dtype, key):
    return draw(_leaf_key(key, name), name, shape, dtype).astype(jnp.float32)


@partial(jax.jit, static_argnums=(0, 4))
def _layer(Dt, x, w, lengths, low):
    D = dict(Dt)
    B, T, _ = x.shape
    hq, hkv, hd = D["hq"], D["hkv"], D["hd"]
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    h = _rmsnorm(x, w["norm1/scale"], D["eps"])
    q, k, v = (_mm(h, w[f"mixer/w{n}"], low) for n in "qkv")
    if D["bias"]:
        q, k, v = q + w["mixer/bq"], k + w["mixer/bk"], v + w["mixer/bv"]
    q = _rope(q.reshape(B, T, hq, hd), pos, D["theta"])
    k = _rope(k.reshape(B, T, hkv, hd), pos, D["theta"])
    v = v.reshape(B, T, hkv, hd)
    g = hq // hkv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = _mm(q, k, low, "bthd,bshd->bhts") / np.sqrt(hd)
    keep = (pos[:, None, :, None] >= pos[:, None, None, :]) & \
        (pos[:, None, None, :] < lengths[:, None, None, None])
    s = jnp.where(keep, s, -jnp.inf)
    a = _mm(jax.nn.softmax(s, -1), v, low, "bhts,bshd->bthd")
    x = x + _mm(a.reshape(B, T, hq * hd), w["mixer/wo"], low)
    h = _rmsnorm(x, w["norm2/scale"], D["eps"])
    up = _mm(h, w["mlp/w_in"], low)
    gate = _mm(h, w["mlp/w_gate"], low)
    return x + _mm(jax.nn.silu(gate) * up, w["mlp/w_out"], low)


@partial(jax.jit, static_argnums=(0, 5))
def _head(Dt, x, rows, cols, g, low, w_out):
    D = dict(Dt)
    h = _rmsnorm(x[rows, cols], g, D["eps"])                   # (N, d)
    if D["tied"]:
        return _mm(h, w_out, low, "nd,vd->nv")
    return _mm(h, w_out, low, "nd,dv->nv")


def logits_at(model: dict, key, seqs: list, positions: list,
              mode: str = "f32") -> list:
    """Reference logits of each sequence at the given positions.

    seqs: token-id lists; positions[i]: positions of seqs[i] whose
    next-token logits are wanted.  Returns one (len(positions[i]), V)
    float32 array per sequence."""
    if mode not in ("f32", "fp8", "bf16"):
        raise ValueError(f"unknown reference mode {mode!r}")
    low = None if mode == "f32" else mode
    D = dims(model)
    Dt = tuple(sorted(D.items()))
    T = -(-max(len(s) for s in seqs) // PAD) * PAD
    toks = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    lengths = jnp.asarray([len(s) for s in seqs], jnp.int32)
    embed = _global_weight("embed", (D["V"], D["d"]), D["dtype"], key)
    x = embed[jnp.asarray(toks)]
    for layer in range(D["L"]):
        x = _layer(Dt, x, _layer_weights(Dt, key, layer), lengths, low)
    if D["tied"]:
        w_out = embed
    else:
        del embed
        w_out = _global_weight("lm_head", (D["d"], D["V"]), D["dtype"], key)
    g = _global_weight("final_norm/scale", (D["d"],), D["dtype"], key)
    rows = np.concatenate([np.full(len(p), i) for i, p in
                           enumerate(positions)]).astype(np.int32)
    cols = np.concatenate([np.asarray(p) for p in positions]).astype(np.int32)
    out = np.asarray(_head(Dt, x, rows, cols, g, low, w_out))
    return np.split(out, np.cumsum([len(p) for p in positions])[:-1])
