"""Operations and bytes that the model's work needs, from shapes alone.

These count what the algorithm needs, not what an implementation does:
a padded block table, a copied page pool or logits computed and thrown
away are not counted.  So the same work is counted whatever implements
it, and a share of a peak cannot read above 100% from a stale count.

The ``attn`` scope of the model (``models/transformer.py``) holds the
q/k/v projections with their biases, the rotary embedding, the write of
the new K/V entries, the attention itself and the output projection;
``attention_*`` counts all of that.  Per row and layer:

    flops  2*d*(hq + 2*hkv)*hd + 2*hq*hd*d       projections
           + 4*hq*hd*live                       scores and weighted sum
    bytes  live*2*hkv*hd*kv_bytes               K and V read once
           + 2*hkv*hd*kv_bytes                  the new entry written
           + 2*hq*hd*act + 2*d*act              q, output, x in, y out
    and once per call and layer the projection weights.

``live`` is the number of cached entries the row attends to.  For a
prefill chunk of C tokens at offset t0, token j attends to t0 + j + 1
entries and the chunk reads the t0 + C entries once.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    d: int
    hq: int
    hkv: int
    hd: int
    f: int
    V: int
    L: int
    bias: bool = False
    act: int = 2            # bytes per activation / weight element
    kv: int = 2             # bytes per cached K or V element

    @classmethod
    def of(cls, model: dict) -> "Dims":
        d, hq = model["hidden_size"], model["num_attention_heads"]
        return cls(d=d, hq=hq, hkv=model["num_key_value_heads"],
                   hd=model.get("head_dim", d // hq),
                   f=model["intermediate_size"], V=model["vocab_size"],
                   L=model["num_hidden_layers"],
                   bias=model.get("qkv_bias", False))

    @property
    def proj_params(self) -> int:
        return self.d * (self.hq + 2 * self.hkv) * self.hd \
            + self.hq * self.hd * self.d

    @property
    def layer_params(self) -> int:
        return self.proj_params + 3 * self.d * self.f


def attention(m: Dims, live: list[int]) -> tuple[float, float]:
    """(flops, bytes) of the attn scope for rows that attend to ``live``
    entries each (a decode step: one row per active request)."""
    n, tot = len(live), sum(live)
    flops = m.L * (2 * n * m.proj_params + 4 * m.hq * m.hd * tot)
    byts = m.L * (tot * 2 * m.hkv * m.hd * m.kv
                  + n * (2 * m.hkv * m.hd * m.kv
                         + 2 * m.hq * m.hd * m.act + 2 * m.d * m.act)
                  + (m.proj_params + (m.hq + 2 * m.hkv) * m.hd * m.bias)
                  * m.act)
    return float(flops), float(byts)


def attention_chunk(m: Dims, t0: int, c: int) -> tuple[float, float]:
    """(flops, bytes) of the attn scope for one prefill chunk."""
    attended = c * t0 + c * (c + 1) // 2
    flops = m.L * (2 * c * m.proj_params + 4 * m.hq * m.hd * attended)
    byts = m.L * ((t0 + c) * 2 * m.hkv * m.hd * m.kv
                  + c * (2 * m.hkv * m.hd * m.kv
                         + 2 * m.hq * m.hd * m.act + 2 * m.d * m.act)
                  + (m.proj_params + (m.hq + 2 * m.hkv) * m.hd * m.bias)
                  * m.act)
    return float(flops), float(byts)


def model_flops(m: Dims, tokens: int, attended: int, logit_rows: int) -> float:
    """Operations the model needs: every layer's matrix products for
    ``tokens`` tokens, attention over ``attended`` (token, entry) pairs,
    and the output head for ``logit_rows`` rows (a chunk needs only its
    last row's logits)."""
    return float(2 * tokens * m.L * m.layer_params
                 + 4 * m.L * m.hq * m.hd * attended
                 + 2 * logit_rows * m.d * m.V)


def decode_flops(m: Dims, live: list[int]) -> float:
    return model_flops(m, len(live), sum(live), len(live))


def chunk_flops(m: Dims, t0: int, c: int) -> float:
    return model_flops(m, c, c * t0 + c * (c + 1) // 2, 1)
