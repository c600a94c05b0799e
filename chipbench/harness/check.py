"""Whether what the timed path served is correct: a comparison of logits.

After the window has closed and the engine is freed, a sample of the
requests that finished, drawn from the seed and always holding the
longest, is run once through the plain reference (prompt plus served
tokens, teacher-forced).  At every position where the engine emitted a
token, the gap is the reference's best logit minus the reference's logit
of the served token: 0 where the engine chose what the reference would,
small where bfloat16 rounding broke a near tie, large where the served
path computed something else.  The number compared is the widest gap.

The control puts the reference in the engine's place one precision below
the configuration's (float8 for bfloat16): at the same positions, its
served tokens are those that the lower precision puts first, and they go
through the same comparison and the same limit (``control=True``).
"""
from __future__ import annotations

import numpy as np

from chipbench.harness import spec
from chipbench.harness.traffic import _rng, prompt_tokens
from chipbench.harness.weights import seed_key


def sample(reqs: list, seed: int, n: int, tokens: int = 0) -> list:
    """Finished requests drawn from the seed, the longest first: at least
    ``n`` of them, and more until they hold ``tokens`` served tokens."""
    done = [r for r in reqs if r.status == "done" and r.generated]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.generated),
                                       -r.rid))
    rest = [r for r in done if r is not longest]
    out, have = [longest], len(longest.generated)
    for i in _rng(seed, 2).permutation(len(rest)):
        if len(out) >= n and have >= tokens:
            break
        out.append(rest[i])
        have += len(rest[i].generated)
    return out


def reference_logits(model: dict, seed: int, picked: list, mode: str,
                     base=spec.BENCH_DIR) -> tuple:
    """(reference logits per request at its served positions, served
    tokens per request)."""
    ref = spec.reference_module(model["reference"], base)
    logits, served = [], []
    for r in picked:     # one at a time: few shapes, and it fits
        prompt = prompt_tokens(seed, r.rid, r.prompt_len, model["vocab_size"])
        pos = list(range(r.prompt_len - 1,
                         r.prompt_len - 1 + len(r.generated)))
        logits += ref.logits_at(model, seed_key(seed),
                                [prompt + list(r.generated[:-1])], [pos],
                                mode)
        served.append(np.asarray(r.generated, np.int64))
    return logits, served


def gaps(ref: list, chosen: list) -> np.ndarray:
    """Per position: reference best logit minus its logit of ``chosen``."""
    return np.concatenate([lg.max(-1) - lg[np.arange(len(c)), c]
                           for lg, c in zip(ref, chosen)])


def served_readings(model: dict, seed: int, reqs: list,
                    base=spec.BENCH_DIR, control: bool = False) -> dict:
    """The numbers the run compares, each with its limit; with
    ``control`` those of the float8 reference put in the program's place."""
    lim = model["check"]
    picked = sample(reqs, seed, lim["sample_requests"], lim["min_tokens"])
    out = {"tokens_checked": {"value": 0, "limit": lim["min_tokens"],
                              "rule": ">="},
           "logit_gap_max": {"value": float("inf"),
                             "limit": lim["logit_gap_max"], "rule": "<="}}
    if not picked:
        return out
    ref, served = reference_logits(model, seed, picked, "f32", base)
    if control:
        low, _ = reference_logits(model, seed, picked, "fp8", base)
        served = [lg.argmax(-1) for lg in low]
    g = gaps(ref, served)
    finite = bool(np.all(np.isfinite(g)))
    out["tokens_checked"]["value"] = int(g.size)
    out["logit_gap_max"]["value"] = float(g.max()) if finite \
        else float("inf")
    return out


def passes(readings: dict) -> bool:
    ok = True
    for r in readings.values():
        v, lim = r["value"], r["limit"]
        ok &= (v >= lim) if r["rule"] == ">=" else (v <= lim)
    return bool(ok)
