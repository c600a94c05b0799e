"""PlanExecutor: compile and run a Trace under a LaunchPlan.

Each plan segment becomes one jitted callable (= one host dispatch, the
``cudaLaunchKernel`` analogue the paper counts).  Compiled segments live in
a process-wide LRU cache keyed by (trace, plan, input shapes/dtypes), so
re-planning or re-instantiating an executor over the SAME trace (e.g.
comparing eager vs chain vs auto during plan search) never pays the
segment-build + jit cost twice.  Distinct traces never share entries —
their jitted closures capture the trace's own constants — which is why
the cache is bounded: old traces' entries age out instead of pinning
their constant arrays forever.
"""
from __future__ import annotations

import statistics
import time
from collections import OrderedDict
from typing import Optional

import jax
import jax.extend.core as jexc

from repro.core.tracing import Trace, _is_drop, _read
from repro.kernels import pallas_interpret
from repro.runtime.plan import LaunchPlan, segment_label
from repro.runtime.rules import get_rule, segment_free_outs

# (trace.token, plan.key(), input signature) -> [(jitted fn, free vars, outs)]
_SEG_CACHE: OrderedDict = OrderedDict()
_CACHE_STATS = {"hits": 0, "misses": 0}
_CACHE_MAX_ENTRIES = 64


def cache_stats() -> dict:
    return dict(_CACHE_STATS)


def clear_cache() -> None:
    _SEG_CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0)


def _args_signature(args) -> tuple:
    """Shape/dtype signature of a flattened arg pytree."""
    sig = []
    for leaf in jax.tree.leaves(args):
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", type(leaf).__name__)
        sig.append((tuple(shape), str(dtype)))
    return tuple(sig)


class PlanExecutor:
    """Executes a trace segment-by-segment under a LaunchPlan.

    ``recorder`` (a ``repro.telemetry.spans.SpanRecorder``) captures one
    host-dispatch span per segment launch — the measured counterpart of
    the simulated host lane in ``core.export``.  Timestamps are RAW
    ``perf_counter`` values: fine on their own, but do not share one
    recorder with ``ServeEngine``, whose spans sit on its virtual serving
    clock — the engine instead re-lays these segment times onto its clock
    itself (``_record_segments``) so merged traces stay aligned.
    """

    def __init__(self, trace: Trace, plan: Optional[LaunchPlan] = None, *,
                 recorder=None):
        self.trace = trace
        self.plan = (plan or LaunchPlan.eager(len(trace.kernels)))
        self.plan.validate(len(trace.kernels))
        self.recorder = recorder
        self._compiled = None
        self._seg_ops = None

    def segment_operators(self) -> list:
        """Per-segment {canonical op -> member-kernel count} maps (lazily
        built once per executor; the plan is immutable)."""
        if self._seg_ops is None:
            from repro.telemetry.attribution import segment_ops
            self._seg_ops = [segment_ops(self.trace.kernels, seg)
                             for seg in self.plan.segments]
        return self._seg_ops

    # ------------------------------------------------------------ compile
    def _build(self):
        key = (self.trace.token, self.plan.key(),
               _args_signature(self.trace.example_args))
        cached = _SEG_CACHE.get(key)
        if cached is not None:
            _CACHE_STATS["hits"] += 1
            _SEG_CACHE.move_to_end(key)
            self._compiled = cached
            return cached
        _CACHE_STATS["misses"] += 1

        flat = self.trace.flat_eqns
        rule_map = dict(self.plan.rules)
        seg_fns = []
        for si, seg in enumerate(self.plan.segments):
            eqns, free, outs = segment_free_outs(flat, seg)

            if si in rule_map:
                # rule-tagged segment: ONE fused kernel replaces the
                # eqn replay (match re-bound here so cached plans stay
                # self-describing; Pallas interprets off-TPU only)
                rule = get_rule(rule_map[si])
                match = rule.bind(self.trace, seg[0])
                if match is None:
                    raise ValueError(
                        f"plan tags segment {si} with rule "
                        f"{rule_map[si]!r} but the trace window no "
                        "longer matches")
                fused_fn, outs = rule.lower(match, free,
                                            interpret=pallas_interpret())
                seg_fns.append((jax.jit(fused_fn), free, outs))
                continue

            def seg_fn(vals, _eqns=eqns, _free=free):
                env = dict(zip(_free, vals))

                def read(v):
                    if isinstance(v, jexc.Literal):
                        return v.val
                    if isinstance(v, tuple):
                        if v[0] == "const":
                            return v[1]
                        return read(v[1])
                    return env[v]

                results = []
                for eqn, invars in _eqns:
                    invals = [read(v) for v in invars]
                    out = eqn.primitive.bind(*invals, **eqn.params)
                    if not eqn.primitive.multiple_results:
                        out = [out]
                    for ov, o in zip(eqn.outvars, out):
                        if not _is_drop(ov):
                            env[ov] = o
                            results.append(o)
                return results

            seg_fns.append((jax.jit(seg_fn), free, outs))
        _SEG_CACHE[key] = seg_fns
        while len(_SEG_CACHE) > _CACHE_MAX_ENTRIES:
            _SEG_CACHE.popitem(last=False)
        self._compiled = seg_fns
        return seg_fns

    # ------------------------------------------------------------ execute
    def run(self, *args, measure: bool = False):
        """Execute all segments; returns (flat outputs, host time/segment)."""
        trace = self.trace
        closed = trace.closed
        segs = self._compiled or self._build()
        env = {}
        for cv, cval in zip(closed.jaxpr.constvars, closed.consts):
            env[cv] = cval
        flat_args = jax.tree.leaves(args)
        for iv, val in zip(closed.jaxpr.invars, flat_args):
            env[iv] = val

        host_times = []
        rec = self.recorder
        for si, (jfn, free, outs) in enumerate(segs):
            vals = [env[v] if not isinstance(v, tuple) else v[1]
                    for v in free]
            t0 = time.perf_counter()
            res = jfn(vals)
            t1 = time.perf_counter()
            if measure:
                jax.block_until_ready(res)
            host_times.append(t1 - t0)
            if rec is not None and rec.enabled:
                rec.add(segment_label(self.trace.kernels,
                                      self.plan.segments[si]),
                        "dispatch", t0, t1, tid=1, segment=si,
                        ops=self.segment_operators()[si])
            for v, o in zip(outs, res):
                env[v] = o

        def read_out(v):
            if isinstance(v, jexc.Literal):
                return v.val
            r = trace.env_map.get(v, v)
            return _read(env, r)

        outputs = [read_out(v) for v in closed.jaxpr.outvars]
        return outputs, host_times

    def call(self, *args):
        """Like run(), but returns outputs re-packed into the traced
        function's original output pytree (engine-facing API)."""
        return self.call_timed(*args)[0]

    def call_timed(self, *args):
        """call() plus the measured per-segment host dispatch times —
        the engine's measured launch tax for one invocation."""
        outputs, host_times = self.run(*args)
        if self.trace.out_tree is not None:
            outputs = jax.tree.unflatten(self.trace.out_tree, outputs)
        return outputs, host_times

    def measure_host(self, *args, repeats: int = 3):
        """Warm up (compile) then measure median per-segment dispatch time."""
        self.run(*args)  # warmup/compile
        all_times = []
        for _ in range(repeats):
            _, ts = self.run(*args, measure=False)
            all_times.append(ts)
        med = [statistics.median(x) for x in zip(*all_times)]
        if self.plan.n_launches == len(self.trace.kernels):
            for k, t in zip(self.trace.kernels, med):
                k.host_dispatch_s = t
        return med

    @property
    def n_launches(self) -> int:
        return self.plan.n_launches
