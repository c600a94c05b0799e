"""One general traffic generator, read from ``traffic/<name>.json``.

Requests arrive open loop, as a Poisson process at a fixed rate.  The seed
changes the order of the work and the token ids, never its amount: lengths
and inter-arrival gaps are the distribution's quantiles at evenly spaced
probabilities, prompt and output quantiles are paired by a fixed shuffle
(the same for every seed), and ``--seed`` permutes the pairs and the gaps.
This is done separately for the lead-in and for the window, each one's
gaps scaled to fill it, so every seed puts the same requests, with the
same lengths, into the measured window.  Two seeds then differ about as
little as two runs of one seed.

A mix file holds:

    arrival       "poisson", the one arrival process
    rate_rps      mean arrival rate, requests per second
    lead_in_s     seconds of traffic before the measured window opens
    drain_s       seconds after the window to wait for first tokens
    prompt, output    {"kind": "lognormal", "median", "sigma", "min", "max"}
    round_prompt_to   prompt lengths are rounded up to a multiple of this
    max_total     a request's prompt plus output tokens at most (the
                  model's context): an output is cut to fit its prompt
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

ARRIVALS = ("poisson",)


@dataclass
class Req:
    """One generated request; ``due_s`` counts from the start of traffic."""
    rid: int
    prompt_len: int
    max_new: int
    due_s: float = 0.0
    stamps: list = field(default_factory=list)   # host clock, one per token
    status: str = "unsent"                       # engine status once done
    generated: list = field(default_factory=list)
    due_abs: float = math.nan                    # host clock


def quantile(dist: dict, p: float) -> float:
    if dist["kind"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    return dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(p))


def lengths(dist: dict, n: int, round_to: int = 1) -> list[int]:
    """``n`` lengths at the distribution's quantiles ``(i + 0.5) / n``,
    clipped to [min, max] and rounded up to a multiple of ``round_to``."""
    out = []
    for i in range(n):
        v = min(max(round(quantile(dist, (i + 0.5) / n)), dist["min"]),
                dist["max"])
        out.append(int(-(-v // round_to) * round_to))
    return out


def pairs(mix: dict, n: int) -> list[tuple[int, int]]:
    """``n`` (prompt, output) lengths, the same for every seed: quantiles
    paired by a fixed shuffle, each output cut to ``max_total`` - prompt."""
    p = lengths(mix["prompt"], n, mix.get("round_prompt_to", 1))
    o = lengths(mix["output"], n)
    total = mix.get("max_total")
    fixed = np.random.default_rng(n).permutation(n)
    return [(p[i], o[j] if total is None else min(o[j], total - p[i]))
            for i, j in enumerate(fixed)]


def _gaps(rate: float, n: int) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, *stream])


def generate(mix: dict, seed: int, seconds: float) -> list[Req]:
    """The requests of one run, in the order they become due."""
    if mix["arrival"] not in ARRIVALS:
        raise ValueError(f"unknown arrival {mix['arrival']!r}; expected "
                         f"{ARRIVALS}")
    order = _rng(seed, 0)
    lead = mix.get("lead_in_s", 0.0)
    out: list[Req] = []
    for start, span in ((0.0, lead), (lead, seconds)):
        n = round(mix["rate_rps"] * span)
        if n <= 0:
            continue
        lens = pairs(mix, n)
        g = _gaps(1.0, n)[order.permutation(n)]
        c = np.cumsum(g)
        due = start + span * (c - g / 2) / c[-1]
        for k, i in enumerate(order.permutation(n)):
            out.append(Req(rid=len(out), prompt_len=lens[i][0],
                           max_new=lens[i][1], due_s=float(due[k])))
    return out


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> list:
    """The prompt of request ``rid``: uniform token ids from the seed."""
    return _rng(seed, 1, rid).integers(0, vocab, length).tolist()
