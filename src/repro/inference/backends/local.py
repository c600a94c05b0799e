"""Single-device execution backend — the engine's original device path.

Holds the four step bodies (contiguous prefill/decode, paged chunk/decode)
as plain functions, dispatched either through ``jax.jit`` closures
(``plan="jit"``) or through the launch-plan runtime (every other strategy:
the body is traced once, a ``LaunchPlan`` is chosen, and each call executes
the plan's compiled segments so real dispatch counts and modeled TKLQT are
observable).  This is byte-for-byte the execution logic that used to live
inline in ``ServeEngine``; only the accounting moved into ``CallAccount``.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.inference.backends.base import (AccountingMixin, BackendInfo,
                                           CallAccount)
from repro.inference.backends.bodies import make_step_bodies
from repro.models import make_cache
from repro.telemetry.spans import profiler_span


class _PlannedFn:
    """One engine callable routed through the launch-plan runtime.

    Traced and planned lazily on first call (shapes are only known then);
    afterwards every call executes the chosen plan's compiled segments,
    which are shared process-wide via the runtime's segment cache.
    """

    def __init__(self, fn, strategy: str, platform: str,
                 lengths=(2, 4, 8, 16, 32)):
        self.fn = fn
        self.strategy = strategy
        self.platform = platform
        self.lengths = lengths
        self.executor = None
        self.plan = None                # chosen LaunchPlan (after _build)
        self.modeled_tklqt_s = 0.0      # modeled TKLQT of ONE invocation
        self.modeled_events = []        # simulated device timeline, one call
        self.last_host_times = []       # measured per-segment dispatch, last call
        self.segment_ops = ()           # per-segment {op -> kernel count}
        self.attribution = None         # AttributionReport, one invocation

    def _build(self, *args):
        """Trace the body, choose the LaunchPlan for this strategy, and
        compile the per-segment executor (once, on first call)."""
        from repro.core.tracing import trace_fn
        from repro.runtime import LaunchPlan, PlanExecutor, Planner
        trace = trace_fn(self.fn, *args)
        planner = Planner(trace, self.platform)
        n = len(trace.kernels)
        if self.strategy == "eager":
            plan = LaunchPlan.eager(n)
        elif self.strategy == "whole_graph":
            plan = LaunchPlan.whole_graph(n)
        elif self.strategy == "chain":
            plan = planner.compare(
                [planner.chain(L) for L in self.lengths])[0].plan
        elif self.strategy == "auto":
            plan = planner.auto(lengths=self.lengths).plan
        elif self.strategy == "fused":
            plan = planner.fused_rules(lengths=self.lengths)
        else:
            raise ValueError(f"unknown plan strategy {self.strategy!r}")
        self.plan = plan
        self.executor = PlanExecutor(trace, plan)
        self.modeled_tklqt_s = planner.evaluate(plan).tklqt
        from repro.runtime.planner import simulate_plan
        self.modeled_events = simulate_plan(trace.kernels, plan, planner.spec)
        from repro.runtime.plan import segment_label
        self.segment_names = [segment_label(trace.kernels, s)
                              for s in plan.segments]
        # operator->kernel attribution of ONE call: per-segment op maps
        # plus the modeled timeline split across issuing operators —
        # computed once here, constant for every later invocation
        from repro.telemetry.attribution import attribute_events
        self.segment_ops = tuple(self.executor.segment_operators())
        self.attribution = attribute_events(trace.kernels, plan,
                                            self.modeled_events)

    def __call__(self, *args):
        if self.executor is None:
            self._build(*args)
        out, self.last_host_times = self.executor.call_timed(*args)
        return out

    @property
    def n_launches(self) -> int:
        """Host dispatches per invocation (0 before first build)."""
        return self.executor.n_launches if self.executor else 0

    @property
    def rule_names(self) -> list:
        """Fusion-rule names overlaid on the chosen plan."""
        return self.plan.rule_names() if self.plan is not None else []


class LocalBackend(AccountingMixin):
    """Default single-device backend (jit or launch-plan dispatch).

    Params and caches are committed to ``device`` (default: the first
    device), so every step runs there — a replica fleet gives each
    engine its own device this way.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int,
                 max_len: int, plan: str = "jit",
                 platform: str = "TPU-v5e", device=None):
        self.cfg = cfg
        self.device = device if device is not None else jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.B = max_batch
        self.T = max_len
        self.plan = plan
        self.platform = platform
        self.info = BackendInfo(kind="local", tp=1,
                                devices=(self.device.id,))
        self._init_accounting()
        self._planned_prefill: dict = {}    # (bucket, plen) -> _PlannedFn
        self._planned_decode: Optional[_PlannedFn] = None

        bodies = make_step_bodies(cfg)      # shared numerics (see bodies.py)
        self._prefill = jax.jit(bodies.prefill, static_argnames=("plen",))
        self._decode = jax.jit(bodies.decode)
        self._prefill_paged = jax.jit(bodies.paged_prefill)
        self._decode_paged = jax.jit(bodies.paged_decode)
        self._verify = jax.jit(bodies.verify)
        self._verify_paged = jax.jit(bodies.paged_verify)
        # planned modes trace with unroll=True: the unrolled layer stack
        # gives the periodic kernel stream proximity mining feeds on
        self._prefill_body = bodies.prefill
        self._decode_body = bodies.decode
        self._paged_prefill_body = bodies.paged_prefill
        self._paged_decode_body = bodies.paged_decode

    # ------------------------------------------------------------ caches
    def init_contiguous_cache(self):
        """Fresh per-slot contiguous KV cache on the backend's device."""
        with jax.default_device(self.device):
            cache = make_cache(self.cfg, self.B, self.T, src_len=1,
                               dtype=self.cfg.cdtype)
        return jax.device_put(cache, self.device)

    def init_paged_cache(self, kv):
        """Fresh pooled KV pages on the backend's device."""
        with jax.default_device(self.device):
            pages = kv.make_pages()
        return jax.device_put(pages, self.device)

    # ------------------------------------------------------------ helpers
    def _planned_account(self, pf: _PlannedFn) -> CallAccount:
        """Charge one launch-plan call: measured per-segment dispatch
        times plus the plan's modeled TKLQT and attribution."""
        return self._charge(CallAccount(
            dispatches=pf.n_launches,
            host_time_s=sum(pf.last_host_times),
            modeled_tklqt_s=pf.modeled_tklqt_s,
            rule_names=tuple(pf.rule_names),
            segment_names=tuple(pf.segment_names),
            segment_host_times=tuple(pf.last_host_times),
            segment_ops=pf.segment_ops,
            attribution=pf.attribution))

    def _jit_account(self, t0: float) -> CallAccount:
        """Charge one jit call: a single dispatch, measured host time."""
        return self._charge(CallAccount(
            dispatches=1, host_time_s=time.perf_counter() - t0))

    # ------------------------------------------------------------ steps
    def prefill(self, cache, tokens, slot: int, plen: int):
        """Write one prompt into a contiguous-cache slot; returns
        (last-position logits, updated cache)."""
        if self.plan == "jit":
            t0 = time.perf_counter()
            with profiler_span("launch.prefill_body"):
                logits, cache = self._prefill(self.params, cache, tokens,
                                              slot, plen)
            self._jit_account(t0)
            return logits, cache
        bucket = tokens.shape[1]
        pf = self._planned_prefill.get((bucket, plen))
        if pf is None:
            fn = functools.partial(self._prefill_body, plen=plen,
                                   unroll=True)
            pf = _PlannedFn(fn, self.plan, self.platform)
            self._planned_prefill[(bucket, plen)] = pf
        with profiler_span("launch.prefill_body"):
            logits, cache = pf(self.params, cache, tokens,
                               jnp.asarray(slot, jnp.int32))
        self._planned_account(pf)
        return logits, cache

    def decode(self, cache, tokens, lengths):
        """One batched decode step over the contiguous cache."""
        if self.plan == "jit":
            t0 = time.perf_counter()
            with profiler_span("launch.decode_body"):
                logits, cache = self._decode(self.params, cache, tokens,
                                             lengths)
            self._jit_account(t0)
            return logits, cache
        if self._planned_decode is None:
            self._planned_decode = _PlannedFn(
                functools.partial(self._decode_body, unroll=True),
                self.plan, self.platform)
        with profiler_span("launch.decode_body"):
            logits, cache = self._planned_decode(self.params, cache, tokens,
                                                 lengths)
        self._planned_account(self._planned_decode)
        return logits, cache

    def prefill_chunk(self, cache, tokens, bt_row, t0_index):
        """Write one prompt chunk into paged KV through a block table."""
        if self.plan == "jit":
            t0 = time.perf_counter()
            with profiler_span("launch.prefill_chunk"):
                logits, cache = self._prefill_paged(self.params, cache,
                                                    tokens, bt_row, t0_index)
            self._jit_account(t0)
            return logits, cache
        chunk_len = tokens.shape[1]
        pf = self._planned_prefill.get(("paged", chunk_len))
        if pf is None:
            fn = functools.partial(self._paged_prefill_body, unroll=True)
            pf = _PlannedFn(fn, self.plan, self.platform)
            self._planned_prefill[("paged", chunk_len)] = pf
        with profiler_span("launch.prefill_chunk"):
            logits, cache = pf(self.params, cache, tokens, bt_row, t0_index)
        self._planned_account(pf)
        return logits, cache

    def paged_decode(self, cache, tokens, lengths, block_tables):
        """One batched decode step gathering KV through block tables."""
        if self.plan == "jit":
            t0 = time.perf_counter()
            with profiler_span("launch.decode"):
                logits, cache = self._decode_paged(self.params, cache,
                                                   tokens, lengths,
                                                   block_tables)
            self._jit_account(t0)
            return logits, cache
        if self._planned_decode is None:
            self._planned_decode = _PlannedFn(
                functools.partial(self._paged_decode_body, unroll=True),
                self.plan, self.platform)
        with profiler_span("launch.decode"):
            logits, cache = self._planned_decode(self.params, cache, tokens,
                                                 lengths, block_tables)
        self._planned_account(self._planned_decode)
        return logits, cache

    def verify(self, cache, tokens, lengths):
        """Speculative verify: score k+1 positions in one forward."""
        # speculative verify is jit-dispatched in every plan mode: the
        # launch-plan runtime replays fixed single-token streams, and the
        # draft/verify launch trade is priced by Planner(draft_launches=)
        # / telemetry.characterize.spec_sweep instead
        t0 = time.perf_counter()
        with profiler_span("launch.verify_body"):
            logits, cache = self._verify(self.params, cache, tokens, lengths)
        self._jit_account(t0)
        return logits, cache

    def paged_verify(self, cache, tokens, lengths, block_tables):
        """Paged-cache variant of ``verify``."""
        t0 = time.perf_counter()
        with profiler_span("launch.paged_verify_body"):
            logits, cache = self._verify_paged(self.params, cache, tokens,
                                               lengths, block_tables)
        self._jit_account(t0)
        return logits, cache

    # ------------------------------------------------------- accounting
    @property
    def planned_decode(self) -> Optional[_PlannedFn]:
        """The decode ``_PlannedFn`` in launch-plan modes (else None)."""
        return self._planned_decode
