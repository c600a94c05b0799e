"""Continuous-batching serving SCHEDULER (Orca/vLLM-style slot scheduler).

This module is the policy half of the engine: a fixed pool of B slots, new
requests prefill into a free slot (prompt lengths padded to power-of-two
buckets to bound recompiles), every step decodes ALL active slots in one
batched step with per-slot lengths, finished slots free immediately and
are refilled from the queue — no head-of-line blocking.  Under
``cache="paged"`` it also runs chunked prefill, the evict-or-preempt
policy, and the host-offload tier over ``repro.kvcache`` block tables.

Everything device-side lives behind the ``ExecutionBackend`` protocol
(``repro.inference.backends``): cache construction/placement, the four
step kinds, plan/fusion dispatch, and per-device launch accounting.  The
scheduler never touches meshes, shard_map, or placement — it manipulates
``Request`` objects, numpy block tables, and whatever cache pytree the
backend hands back.  Backends:

  * ``tp=1`` -> ``LocalBackend``: the single-device path; ``plan="jit"``
    runs whole-step jit closures, any other strategy routes through the
    launch-plan runtime (``repro.runtime``) so ``EngineStats`` reports
    real dispatch counts and modeled TKLQT (``plan="autotuned"`` resolves
    the strategy from a measured plan table).
  * ``tp>1`` -> ``ShardedBackend``: tensor-parallel shard_map serving;
    params/KV head-sharded over a device mesh, per-device dispatch
    streams and collective traffic (psum payloads priced over the
    platform's coupling link) surfaced in ``EngineStats``.

Because admission, preemption, and sampling are scheduler-side and the
backends agree numerically, ``ServeEngine(tp=2)`` drains any workload —
including admit -> preempt -> resume under pool pressure — with greedy
tokens byte-identical to ``tp=1``.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.inference.backends import CallAccount, make_backend
from repro.inference.kv_quant import KV_DTYPES
from repro.inference.speculative import (default_draft_config,
                                         draft_params_from_target,
                                         is_truncation_of, pick_spec_k,
                                         validate_draft)
from repro.telemetry.metrics import RequestTiming
from repro.telemetry.registry import MetricsRegistry
from repro.telemetry.spans import profiler_span, profiling

PLAN_STRATEGIES = ("jit", "eager", "whole_graph", "chain", "auto", "fused",
                   "autotuned")
CACHE_MODES = ("contiguous", "paged")
OFFLOAD_MODES = ("none", "host")


@dataclass
class Request:
    """One serving request: prompt in, greedy continuation out."""

    rid: int
    prompt: list
    max_new_tokens: int = 16
    arrival_s: float = 0.0         # offset on the engine clock (open loop)
    generated: list = field(default_factory=list)
    done: bool = False
    status: str = "queued"         # queued|active|preempted|done|rejected


@dataclass
class _PrefillTask:
    """One in-flight (chunked) prefill: tokens left to write into the
    paged cache for a slot.  ``replay=True`` rebuilds KV for a preempted
    request (prompt + already-emitted tokens) without emitting anything."""
    req: Request
    slot: int
    toks: list
    pos: int = 0                   # tokens already written
    replay: bool = False
    last_logits: Optional[jax.Array] = None


class EngineStats:
    """Serving counters as a DERIVED VIEW of a ``MetricsRegistry``.

    Every scalar field lives in a registry gauge: attribute reads pull the
    gauge value (int-typed fields come back as Python ints), assignments
    and ``+=`` write it.  The engine's counting code is unchanged — but
    ``registry.snapshot()`` and the Prometheus exporter now see exactly
    the numbers the engine reports, with no second bookkeeping path to
    drift.  Per-step series, per-request timings, and other non-scalar
    state stay plain attributes (series belong in histograms, which the
    engine feeds separately).
    """

    # attribute -> (gauge name, python type, help text)
    _SCALARS = {
        "prefills": ("engine_prefills", int, "prefill steps executed"),
        "decode_steps": ("engine_decode_steps", int,
                         "batched decode steps executed"),
        "tokens_out": ("engine_tokens_out", int, "tokens emitted"),
        "prefill_dispatches": ("engine_prefill_dispatches", int,
                               "host dispatches (launches) in prefills"),
        "decode_dispatches": ("engine_decode_dispatches", int,
                              "host dispatches across all decode steps"),
        "fused_dispatches": ("engine_fused_dispatches", int,
                             "decode dispatches that ran fused kernels"),
        "modeled_tklqt_s": ("engine_modeled_tklqt_seconds", float,
                            "device-model TKLQT summed over steps "
                            "(0 under plan=jit: nothing modeled)"),
        "measured_dispatch_s": ("engine_measured_dispatch_seconds", float,
                                "measured host launch tax, all steps"),
        "decode_dispatch_time_s": ("engine_decode_dispatch_seconds", float,
                                   "measured launch tax, decode only"),
        # ---- tensor parallelism (tp=1: one stream, zero collectives)
        "collectives": ("engine_collectives", int,
                        "collective ops issued (psums)"),
        "collective_bytes": ("engine_collective_bytes", int,
                             "payload bytes entering collectives"),
        "decode_collective_bytes": ("engine_decode_collective_bytes", int,
                                    "decode-step-only collective payload"),
        "modeled_collective_tax_s": ("engine_modeled_collective_tax_seconds",
                                     float,
                                     "collectives priced over the link"),
        # ---- paged KV cache (cache="paged"; zero under contiguous)
        "rejected": ("engine_rejected", int,
                     "admissions refused: plen + budget > max_len"),
        "preemptions": ("engine_preemptions", int,
                        "slots evicted under block-pool pressure"),
        "prefill_chunks": ("engine_prefill_chunks", int,
                           "chunked-prefill segments executed"),
        "offload_bytes": ("engine_offload_bytes", int,
                          "measured KV bytes evicted to the host tier"),
        "restore_bytes": ("engine_restore_bytes", int,
                          "measured KV bytes restored from the host tier"),
        "offload_transfers": ("engine_offload_transfers", int,
                              "block DMAs (evict + restore directions)"),
        "modeled_offload_tax_s": ("engine_modeled_offload_tax_seconds",
                                  float,
                                  "offload DMAs priced over the coupling "
                                  "link (core.device_model PCIe/C2C)"),
        # ---- speculative decoding (speculative=True; zero otherwise)
        "spec_rounds": ("engine_spec_rounds", int,
                        "draft-propose + batched-verify rounds"),
        "proposed": ("engine_spec_proposed", int,
                     "draft tokens offered to verification"),
        "accepted": ("engine_spec_accepted", int,
                     "draft tokens accepted AND emitted"),
        "corrections": ("engine_spec_corrections", int,
                        "target correction tokens emitted"),
        "draft_dispatches": ("engine_draft_dispatches", int,
                             "launches on the draft dispatch stream"),
        "modeled_draft_launch_tax_s": (
            "engine_modeled_draft_launch_tax_seconds", float,
            "draft stream launches, platform-priced"),
        # ---- prefix sharing (share_prefix=True; zero otherwise)
        "prefix_adoptions": ("engine_prefix_adoptions", int,
                             "admissions that adopted shared prefix blocks"),
        "shared_prefix_tokens": ("engine_shared_prefix_tokens", int,
                                 "prompt tokens served from shared blocks "
                                 "instead of re-prefilling"),
    }

    def __init__(self, plan: str = "jit", tp: int = 1, registry=None):
        if registry is None:
            from repro.telemetry.registry import MetricsRegistry
            registry = MetricsRegistry()
        object.__setattr__(self, "registry", registry)
        gauges = {}
        for attr, (name, _, help_text) in self._SCALARS.items():
            g = registry.gauge(name, help_text)
            g.set(0)                      # fresh stats zero their gauges
            gauges[attr] = g
        object.__setattr__(self, "_gauges", gauges)
        self.plan = plan
        self.tp = tp                   # device streams every dispatch fans to
        self.slot_occupancy = []
        self.rule_hits = {}            # rule name -> launches
        self.step_times_s = []         # decode step durations
        self.per_device_dispatches = {}
        self.block_pool_utilization = []  # per decode step
        # single source of truth for per-request latency: rid ->
        # RequestTiming (ttft_s/e2e_s/itl_samples_s below are derived)
        self.timings = {}

    def __getattr__(self, name):
        spec = type(self)._SCALARS.get(name)
        if spec is None:
            raise AttributeError(
                f"{type(self).__name__!r} object has no attribute {name!r}")
        try:
            gauges = object.__getattribute__(self, "_gauges")
        except AttributeError:
            raise AttributeError(name) from None
        v = gauges[name].value()
        return int(v) if spec[1] is int else v

    def __setattr__(self, name, value):
        if name in self._SCALARS:
            self._gauges[name].set(value)
        else:
            object.__setattr__(self, name, value)

    @property
    def dispatches_per_decode_step(self) -> float:
        """Mean host dispatches (kernel launches) per decode step."""
        return (self.decode_dispatches / self.decode_steps
                if self.decode_steps else 0.0)

    @property
    def fused_dispatches_per_decode_step(self) -> float:
        """Mean fused-kernel launches per decode step."""
        return (self.fused_dispatches / self.decode_steps
                if self.decode_steps else 0.0)

    @property
    def ttft_s(self) -> dict:
        """Time-to-first-token per request id (first-token seen only)."""
        return {rid: t.ttft_s for rid, t in self.timings.items()
                if not math.isnan(t.first_token_s)}

    @property
    def e2e_s(self) -> dict:
        """End-to-end latency per completed request id."""
        return {rid: t.e2e_s for rid, t in self.timings.items()
                if not math.isnan(t.done_s)}

    @property
    def itl_samples_s(self) -> list:
        """Every inter-token-latency gap across all requests."""
        return [g for t in self.timings.values() for g in t.itl_s]

    @property
    def mean_ttft_s(self) -> float:
        """Mean time-to-first-token over requests that emitted one."""
        ttft = self.ttft_s
        return sum(ttft.values()) / len(ttft) if ttft else 0.0

    @property
    def mean_itl_s(self) -> float:
        """Mean inter-token latency over all sampled gaps."""
        itl = self.itl_samples_s
        return sum(itl) / len(itl) if itl else 0.0

    @property
    def mean_block_pool_utilization(self) -> float:
        """Mean paged block-pool occupancy across sampled steps."""
        u = self.block_pool_utilization
        return sum(u) / len(u) if u else 0.0

    @property
    def peak_block_pool_utilization(self) -> float:
        """Peak paged block-pool occupancy across sampled steps."""
        return max(self.block_pool_utilization, default=0.0)

    @property
    def launch_tax_per_step_s(self) -> float:
        """Measured host dispatch time per engine step (prefill+decode)."""
        steps = self.prefills + self.decode_steps
        return self.measured_dispatch_s / steps if steps else 0.0

    @property
    def launch_tax_per_decode_step_s(self) -> float:
        """Decode-only launch tax per decode step — comparable against the
        mean decode-step latency (the measured boundedness denominator)."""
        return (self.decode_dispatch_time_s / self.decode_steps
                if self.decode_steps else 0.0)

    @property
    def accept_rate(self) -> float:
        """Fraction of proposed draft tokens accepted (and emitted)."""
        return self.accepted / self.proposed if self.proposed else 0.0

    @property
    def spec_emitted(self) -> int:
        """Tokens emitted through speculative rounds (accept + correct)."""
        return self.accepted + self.corrections

    @property
    def steps_per_emitted_token(self) -> float:
        """Sequential target steps per token emitted in spec rounds —
        < 1.0 is the speculation win (plain decode is exactly 1.0)."""
        return (self.spec_rounds / self.spec_emitted
                if self.spec_emitted else 0.0)

    @property
    def collective_bytes_per_decode_step(self) -> float:
        """Decode-only psum payload per decode step (prefill psums are
        tracked in ``collective_bytes`` but excluded here, so the figure
        is a property of the decode step, not the workload shape)."""
        return (self.decode_collective_bytes / self.decode_steps
                if self.decode_steps else 0.0)


class ServeEngine:
    """Continuous-batching serving scheduler over an execution backend.

    The engine is pure policy — slot admission, chunked prefill,
    preempt/offload/resume, greedy sampling, virtual-clock accounting —
    and delegates every device interaction (cache placement, the step
    kinds, launch accounting) to its ``ExecutionBackend``: local
    (``tp=1``), tensor-parallel sharded (``tp>=2``), optionally wrapped
    speculative.  Drive it closed-loop with ``run(requests)`` or
    open-loop/steppable with ``submit()`` + ``tick()`` (the replica-
    fleet router uses the latter).
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 256, greedy: bool = True,
                 plan: str = "jit", platform: str = "TPU-v5e",
                 plan_table=None, telemetry=None, tp: int = 1,
                 backend=None,
                 cache: str = "contiguous", block_size: int = 16,
                 num_blocks: Optional[int] = None, offload: str = "none",
                 prefill_chunk: Optional[int] = None,
                 kv_dtype: str = "bf16", share_prefix: bool = False,
                 prefix_len: int = 8,
                 speculative: bool = False, draft_config=None,
                 draft_params=None, spec_k: int = 4,
                 spec_inflection: Optional[int] = None, monitor=True,
                 tracer=None, device=None):
        if plan not in PLAN_STRATEGIES:
            raise ValueError(f"unknown plan {plan!r}; "
                             f"expected one of {PLAN_STRATEGIES}")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch} "
                             "(an engine with no slots can never admit)")
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if cache not in CACHE_MODES:
            raise ValueError(f"unknown cache {cache!r}; "
                             f"expected one of {CACHE_MODES}")
        if offload not in OFFLOAD_MODES:
            raise ValueError(f"unknown offload {offload!r}; "
                             f"expected one of {OFFLOAD_MODES}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if cache != "paged" and (offload != "none"
                                 or prefill_chunk is not None):
            raise ValueError(
                "offload= and prefill_chunk= need cache='paged' (the "
                "contiguous cache has no blocks to evict or chunk over)")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                             f"expected one of {KV_DTYPES}")
        if cache != "paged" and (kv_dtype != "bf16" or share_prefix):
            raise ValueError(
                "kv_dtype= and share_prefix= need cache='paged' (the "
                "contiguous cache has no pages to quantize or share)")
        if prefix_len < 1:
            raise ValueError(f"prefix_len must be >= 1, got {prefix_len}")
        if not speculative and (draft_config is not None
                                or draft_params is not None):
            raise ValueError(
                "draft_config=/draft_params= need speculative=True")
        if speculative:
            if not greedy:
                raise ValueError(
                    "speculative=True requires greedy=True: the accept "
                    "rule matches draft tokens against target ARGMAX — "
                    "sampled decoding has no byte-identical reference "
                    "sequence to preserve")
            if plan != "jit":
                raise ValueError(
                    f"speculative=True executes plan='jit' only (got "
                    f"{plan!r}): the launch-plan runtime replays fixed "
                    "single-token streams; model the draft/verify launch "
                    "trade with telemetry.characterize.spec_sweep or "
                    "runtime.planner.simulate_plan(draft_launches=...)")
        if plan == "autotuned":
            # measured plan table (runtime.autotune): the strategy the
            # autotuner benchmarked best for this slot count
            from repro.runtime.autotune import PlanTable
            if plan_table is None:
                raise ValueError(
                    "plan='autotuned' needs plan_table= (a PlanTable, "
                    "a dict, or a path to a saved plan table)")
            table = (plan_table if isinstance(plan_table, PlanTable)
                     else PlanTable.from_any(plan_table))
            if table.arch and table.arch != cfg.name:
                raise ValueError(
                    f"plan table was autotuned for arch "
                    f"{table.arch!r}, engine config is {cfg.name!r}; "
                    f"re-run repro.launch.autotune for this model")
            if table.d_model and table.d_model != cfg.d_model:
                raise ValueError(
                    f"plan table was autotuned at d_model="
                    f"{table.d_model} (reduced() keeps the arch name), "
                    f"engine config has d_model={cfg.d_model}; re-run "
                    f"repro.launch.autotune against this exact config")
            if table.platform and table.platform != platform:
                raise ValueError(
                    f"plan table was autotuned for platform "
                    f"{table.platform!r}, engine uses {platform!r}; "
                    f"re-run repro.launch.autotune for this platform")
            plan = table.lookup(max_batch)
            self.plan_label = f"autotuned:{plan}"
        else:
            self.plan_label = plan
        self.cfg = cfg
        self.params = params
        self.B = max_batch
        self.T = max_len
        self.cache_mode = cache
        self.prefill_chunk = prefill_chunk
        # the backend owns everything device-side (placement, meshes,
        # compiled steps); pass backend= to serve through a custom one,
        # device= to pin a tp=1 engine to one device
        self.backend = backend if backend is not None else make_backend(
            cfg, params, max_batch=max_batch, max_len=max_len, tp=tp,
            plan=plan, platform=platform, device=device)
        self.speculative = bool(speculative)
        self.spec_k = spec_k
        self.spec_inflection = spec_inflection
        if speculative:
            # wrap whatever target backend was built (local OR sharded —
            # speculation composes with tensor parallelism) with the
            # draft-propose / batched-verify layer
            draft_cfg = (draft_config if draft_config is not None
                         else default_draft_config(cfg))
            validate_draft(cfg, draft_cfg, spec_k)
            if draft_params is None:
                if not is_truncation_of(draft_cfg, cfg):
                    raise ValueError(
                        f"draft config {draft_cfg.name!r} is not a "
                        f"truncation of {cfg.name!r} (different width/"
                        "heads/pattern), so its weights cannot be sliced "
                        "from the target: pass draft_params= explicitly "
                        "(e.g. repro.models.init_params(key, "
                        "draft_config))")
                draft_params = draft_params_from_target(params, draft_cfg)
            from repro.inference.backends.speculative import \
                SpeculativeBackend
            self.backend = SpeculativeBackend(
                self.backend, draft_cfg, draft_params,
                max_batch=max_batch, max_len=max_len, platform=platform)
            self.draft_cfg = draft_cfg
            self.draft_cache = self.backend.init_draft_cache()
            self.draft_lengths = np.zeros(max_batch, np.int32)
        # derived, not stored: an injected backend= decides the degree
        self.tp = self.backend.info.tp
        self.kv_dtype = kv_dtype
        self.share_prefix = bool(share_prefix)
        self.prefix_len = prefix_len
        if cache == "paged":
            from repro.kvcache import (HostOffloadTier, PagedKVCache,
                                       default_num_blocks)
            # default pool sized by BYTES: a quantized pool holds the same
            # byte budget as the bf16 full-capacity pool, in more blocks
            nb = default_num_blocks(max_batch, max_len, block_size,
                                    num_blocks, kv_dtype=kv_dtype,
                                    hd=cfg.hd,
                                    payload_bytes=jnp.dtype(
                                        cfg.cdtype).itemsize)
            self.kv = PagedKVCache(cfg, num_blocks=nb,
                                   block_size=block_size, max_len=max_len,
                                   dtype=cfg.cdtype, kv_dtype=kv_dtype)
            self.cache = self.backend.init_paged_cache(self.kv)
            self.offload_tier = (
                HostOffloadTier(platform, tp=self.backend.info.tp)
                if offload == "host" else None)
        else:
            self.kv = None
            self.offload_tier = None
            self.cache = self.backend.init_contiguous_cache()
        # prefix-sharing donor registry: 8-token prompt-prefix key (the
        # SAME key the router's prefix-affinity policy hashes, so sticky
        # routing lands same-prefix requests where the donor blocks live)
        # -> (donor rid, donor's full verified token sequence)
        self._prefix_donors: dict = {}
        self._prefill_tasks: dict = {}      # slot -> _PrefillTask
        self._preempted: list = []          # evicted Requests awaiting resume
        self._pending: list = []            # submitted, not yet admitted
        self._admit_seq = 0                 # victim ordering (youngest first)
        self._last_step_progressed = True
        self._ticks = 0                     # step_num of the tick span
        self._first_tokens: list = []       # first tokens of this tick
        self.lengths = np.zeros(max_batch, np.int32)
        self.slots: list[Optional[Request]] = [None] * max_batch
        self.registry = MetricsRegistry()
        self.stats = EngineStats(plan=self.plan_label,
                                 tp=self.backend.info.tp,
                                 registry=self.registry)
        self._dev_base = self.backend.device_dispatches  # reset() baseline
        self.greedy = greedy
        self.plan = plan
        self.platform = platform
        self.telemetry = telemetry          # Optional[SpanRecorder]
        # request-scoped lifecycle tracer (Optional[RequestTracer]); a
        # fleet shares ONE instance across replicas so a trace follows
        # its request through re-queue and re-dispatch — reset() leaves
        # it alone for the same reason
        self.tracer = tracer
        # live boundedness monitor: True -> create one, False/None -> off,
        # or pass a BoundednessMonitor instance to share across engines
        if monitor is True:
            from repro.telemetry.monitor import BoundednessMonitor
            self.monitor = BoundednessMonitor()
        elif monitor:
            self.monitor = monitor
        else:
            self.monitor = None
        # virtual serving clock (seconds): advances by measured wall time
        # while the engine works, jumps forward over idle gaps so open-loop
        # arrival schedules don't cost real wall time to honor.  Queue wait
        # and admit-to-token are wall-clock durations instead (perf_counter
        # stamps at submit, first admission, first-token sampling and the
        # tick's end): a live server's requests all arrive at 0 here
        self.now = 0.0
        self._bind_telemetry()

    # ------------------------------------------------------------ internals
    @property
    def timings(self) -> dict:
        """Per-request RequestTiming objects (lives on stats)."""
        return self.stats.timings

    @property
    def _planned_decode(self):
        """The decode _PlannedFn when a launch-plan mode is active
        (kept as an engine attribute for telemetry/tests compat)."""
        return self.backend.planned_decode

    @staticmethod
    def _bucket(n: int) -> int:
        """Round a length to its power-of-two compile bucket (min 8)."""
        return max(8, 1 << (n - 1).bit_length())

    def _free_slot(self) -> Optional[int]:
        """Index of the first open batch slot, or None when full."""
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _sample(self, logits_row) -> int:
        """Greedy token choice from one logits row (the host waits for
        the device here)."""
        with profiler_span("sync", what="argmax"):
            return int(jnp.argmax(logits_row))

    def _absorb(self, acct: CallAccount, *, decode: bool) -> None:
        """Fold one backend call's accounting into EngineStats — the one
        merge path shared by jit, planned, and sharded execution."""
        if decode:
            self.stats.decode_dispatches += acct.dispatches
            self.stats.decode_dispatch_time_s += acct.host_time_s
            self.stats.fused_dispatches += len(acct.rule_names)
            self.stats.decode_collective_bytes += acct.collective_bytes
        else:
            self.stats.prefill_dispatches += acct.dispatches
        self.stats.measured_dispatch_s += acct.host_time_s
        self.stats.modeled_tklqt_s += acct.modeled_tklqt_s
        for nm in acct.rule_names:
            self.stats.rule_hits[nm] = self.stats.rule_hits.get(nm, 0) + 1
        self.stats.collectives += acct.collectives
        self.stats.collective_bytes += acct.collective_bytes
        self.stats.modeled_collective_tax_s += acct.modeled_collective_tax_s
        self.stats.proposed += acct.proposed
        self.stats.accepted += acct.accepted
        self.stats.draft_dispatches += acct.draft_dispatches
        self.stats.modeled_draft_launch_tax_s += \
            acct.modeled_draft_launch_tax_s
        self.stats.per_device_dispatches = {
            d: n - self._dev_base.get(d, 0)
            for d, n in self.backend.device_dispatches.items()}

    def _record_segments(self, acct: CallAccount, t_begin: float) -> None:
        """Per-segment dispatch spans on the engine clock: the measured
        host times of the last planned call, laid out back-to-back from
        the step's start (tid 1 of the merged Chrome trace)."""
        if self.telemetry is None or not self.telemetry.enabled:
            return
        t = t_begin
        for name, h in zip(acct.segment_names, acct.segment_host_times):
            self.telemetry.add(name, "dispatch", t, t + h, tid=1)
            t += h

    # ------------------------------------------------------- observability
    def _bind_telemetry(self) -> None:
        """Point every instrumented component at ``self.registry`` (fresh
        after ``reset()``: gauges restart at zero, histograms empty)."""
        reg = self.registry
        if hasattr(self.backend, "bind_metrics"):
            self.backend.bind_metrics(reg)
        if self.kv is not None:
            self.kv.pool.bind_metrics(reg)
        if self.offload_tier is not None:
            self.offload_tier.bind_metrics(reg)
        if self.telemetry is not None and hasattr(self.telemetry,
                                                  "bind_metrics"):
            self.telemetry.bind_metrics(reg)
        if self.monitor is not None:
            self.monitor.bind_metrics(reg)
        self._h_step = reg.histogram(
            "engine_step_time_seconds", "decode step wall time")
        self._h_ttft = reg.histogram(
            "engine_ttft_seconds",
            "arrival to first emission, engine clock")
        self._h_itl = reg.histogram(
            "engine_itl_seconds", "inter-token latency")
        self._h_queue_wait = reg.histogram(
            "engine_queue_wait_seconds",
            "submit to first admission, wall clock")
        self._h_admit_to_token = reg.histogram(
            "engine_admit_to_token_seconds",
            "first admission to the end of the tick that returns the "
            "first token, wall clock")

    def _note_step(self, batch: int, dt: float, acct: CallAccount) -> None:
        """One decode step into the step-time histogram and the live
        boundedness monitor (measured step time + measured launch tax,
        plus the step's per-operator attribution when a planned mode
        carries one)."""
        if self._h_step is not None:
            self._h_step.observe(dt)
        if self.monitor is not None:
            self.monitor.observe(batch, dt, acct.host_time_s)
            if acct.attribution is not None:
                self.monitor.observe_operators(acct.attribution.rows)

    def _note_first_token(self, req: Request) -> RequestTiming:
        """Record a request's first emission: its RequestTiming plus the
        TTFT histogram sample; the tick that produced it hands it over
        (``_hand_over_first_tokens``)."""
        req._t_sampled = time.perf_counter()
        self._first_tokens.append(req)
        timing = RequestTiming(req.rid, arrival_s=req.arrival_s,
                               first_token_s=self.now)
        timing.token_times_s.append(self.now)
        self.timings[req.rid] = timing
        if self._h_ttft is not None:
            self._h_ttft.observe(max(0.0, self.now - req.arrival_s))
        return timing

    def _admission(self, req: Request, resume: bool = False):
        """The ``repro.admit`` span of one admission.  A first admission
        stamps the wall clock and records the wait since ``submit()``;
        a resumed one (after preemption) keeps its first stamp."""
        if resume:
            return profiler_span("admit", rid=req.rid,
                                 prompt_len=len(req.prompt), resume=1)
        t = req._t_admit = time.perf_counter()
        wait = t - getattr(req, "_t_submit", t)
        self._h_queue_wait.observe(wait)
        return profiler_span("admit", rid=req.rid,
                             prompt_len=len(req.prompt),
                             queue_wait_ms=wait * 1e3)

    def _hand_over_first_tokens(self) -> None:
        """End of a tick: the first tokens it produced reach the caller
        now.  One ``repro.first_token`` span each, with the wall-clock
        split of the wait: submit to admission, admission to now, and
        how long the sampled token was held inside the tick."""
        t = time.perf_counter()
        for req in self._first_tokens:
            t_admit = getattr(req, "_t_admit", req._t_sampled)
            self._h_admit_to_token.observe(t - t_admit)
            if profiling():
                wait = t_admit - getattr(req, "_t_submit", t_admit)
                with profiler_span(
                        "first_token", rid=req.rid,
                        queue_wait_ms=wait * 1e3,
                        admit_to_token_ms=(t - t_admit) * 1e3,
                        holdback_ms=(t - req._t_sampled) * 1e3):
                    pass
        self._first_tokens.clear()

    def _note_token(self, timing) -> None:
        """Record a non-first emission: token time plus the ITL sample
        (gap since the request's previous token on the engine clock)."""
        if timing is None:
            return
        if self._h_itl is not None and timing.token_times_s:
            self._h_itl.observe(
                max(0.0, self.now - timing.token_times_s[-1]))
        timing.token_times_s.append(self.now)

    # ------------------------------------------------------------ api
    def admit(self, req: Request) -> bool:
        """Admit one request into a slot and prefill; False = no room.

        Requests whose prompt + decode budget exceed ``max_len`` are
        rejected outright (status ``rejected``) rather than risking
        out-of-bounds KV writes.
        """
        plen = len(req.prompt)
        if plen + req.max_new_tokens > self.T:
            # the full generation cannot fit the KV region: answer with a
            # rejection instead of letting prefill/decode writes clamp or
            # drop out of bounds (silently corrupted attention)
            req.done = True
            req.status = "rejected"
            self.stats.rejected += 1
            self.timings.setdefault(
                req.rid, RequestTiming(req.rid, arrival_s=req.arrival_s))
            if self.tracer is not None:
                self.tracer.reject(req.rid, self.now)
            return True
        if self.cache_mode == "paged":
            return self._admit_paged(req)
        slot = self._free_slot()
        if slot is None:
            return False
        with self._admission(req):
            bucket = self._bucket(plen)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :plen] = req.prompt
            t0 = time.perf_counter()
            logits, self.cache = self.backend.prefill(
                self.cache, jnp.asarray(toks), slot, plen)
            acct = self.backend.last
            self._absorb(acct, decode=False)
            first = self._sample(logits[0])
        dt = time.perf_counter() - t0
        t_begin = self.now
        self.now += dt
        req.generated.append(first)
        self.stats.prefills += 1
        self.stats.tokens_out += 1
        timing = self._note_first_token(req)
        if self.tracer is not None:
            self.tracer.admit(req.rid, t_begin)
            self.tracer.prefill(req.rid, t_begin, self.now,
                                tax_s=acct.host_time_s)
            self.tracer.first_token(req.rid, self.now)
        if len(req.generated) >= req.max_new_tokens:
            # single-token budget: done at prefill, never occupies a slot
            req.done = True
            req.status = "done"
            timing.done_s = self.now
            if self.tracer is not None:
                self.tracer.done(req.rid, self.now,
                                 n_tokens=len(req.generated))
        else:
            req.status = "active"
            self.slots[slot] = req
            self.lengths[slot] = plen
            if self.speculative:
                self._draft_prefill_slot(slot, req.prompt)
        if self.telemetry is not None:
            self.telemetry.add(f"prefill[{plen}]", "prefill", t_begin,
                               self.now, rid=req.rid, slot=slot, plen=plen)
            self._record_segments(acct, t_begin)
        return True

    # ------------------------------------------------------------ paged api
    def _admit_paged(self, req: Request) -> bool:
        """Paged-cache admission: allocate blocks, start (chunked)
        prefill, or restore/replay a preempted request's KV."""
        slot = self._free_slot()
        if slot is None:
            return False
        resume = getattr(req, "_resume", None)
        if resume is not None and resume[0] == "host":
            return self._restore_from_host(req, slot, resume[1])
        with self._admission(req, resume=resume is not None):
            self._start_prefill(req, slot, resume)
        return True

    def _start_prefill(self, req: Request, slot: int, resume) -> None:
        """Seat ``req`` in ``slot`` and queue its (chunked) prefill."""
        toks = list(req.prompt)
        replay = False
        if resume is not None:
            # recompute-on-resume: rebuild KV by re-prefilling the prompt
            # plus everything already emitted EXCEPT the last token — that
            # one is the next decode step's input, exactly the state the
            # uninterrupted run would be in (greedy decode then continues
            # byte-identically).  A request preempted mid-prefill has
            # emitted nothing: it re-prefills normally (replay=False) and
            # still gets its first token at completion.
            toks = list(req.prompt) + list(req.generated[:-1])
            replay = len(req.generated) > 0
        req._resume = None
        req.status = "active"
        req._admit_seq = self._admit_seq
        self._admit_seq += 1
        self.slots[slot] = req
        self.lengths[slot] = 0
        # prefix sharing: map the leading full blocks of a donor with the
        # same verified token prefix into this request's table, and start
        # the prefill past them (the skipped tokens' KV already exists) —
        # works for fresh admits AND recompute replays, whose rebuilt KV
        # would be byte-identical to the donor pages anyway
        shared = self._adopt_prefix(req, toks) if self.share_prefix else 0
        self._prefill_tasks[slot] = _PrefillTask(
            req=req, slot=slot, toks=toks, pos=shared, replay=replay)
        if self.tracer is not None:
            self.tracer.admit(req.rid, self.now, resume=resume is not None)

    # bound on live donor candidates tracked per prefix key
    _DONORS_PER_KEY = 4

    def _register_donor(self, key, rid: int, toks, written: int) -> None:
        """Add/refresh a donor candidate for ``key``.  ``written`` caps how
        many of ``toks`` have fully-written KV blocks (a finished prefill
        covers its whole prompt; an in-flight adopter only its shared
        region)."""
        cands = self._prefix_donors.setdefault(key, [])
        cands[:] = [c for c in cands if c[0] != rid]
        cands.insert(0, (rid, tuple(toks), written))
        del cands[self._DONORS_PER_KEY:]

    def _adopt_prefix(self, req: Request, toks: list) -> int:
        """Adopt a donor's leading blocks when its verified token sequence
        shares a block-aligned prefix with ``toks``.  Only FULL blocks
        strictly inside the prompt are shared (the final prompt token must
        be re-written so its logits exist), so normal prefill/decode never
        writes into a shared block — ``_cow_protect`` guards the rest.
        Returns the number of prompt tokens covered by adopted blocks."""
        if len(toks) < self.prefix_len:
            return 0
        key = tuple(toks[:self.prefix_len])
        cands = self._prefix_donors.get(key)
        if not cands:
            return 0
        bs = self.kv.block_size
        shared, live = 0, []
        for drid, dtoks, written in cands:
            if drid == req.rid:
                continue
            dblocks = self.kv.pool.owned(drid)
            if not dblocks:
                continue               # donor drained: prune this candidate
            live.append((drid, dtoks, written))
            if shared:
                continue               # already adopted from a fresher donor
            common = 0
            for a, b in zip(dtoks, toks):
                if a != b:
                    break
                common += 1
            common = min(common, written)
            n = min(min(common, len(toks) - 1) // bs, len(dblocks))
            if n <= 0:
                continue
            self.kv.pool.adopt(req.rid, dblocks[:n])
            self.stats.prefix_adoptions += 1
            self.stats.shared_prefix_tokens += n * bs
            shared = n * bs
        if live:
            self._prefix_donors[key] = live
        else:
            self._prefix_donors.pop(key, None)
        if shared:
            # the adopter itself now holds fully-written shared blocks, so
            # it can donate them even before its own prefill finishes —
            # this keeps sharing chains alive across short donor lifetimes
            self._register_donor(key, req.rid, toks, shared)
        return shared

    def _cow_protect(self, rid, start: int, end: int) -> bool:
        """Copy-on-write guard: before a write into token range
        ``[start, end)``, diverge any covering block that is still shared
        (refcount > 1) — copy the page, swap the private block into the
        owner's table.  False = no free block for the copy; the caller
        stalls exactly like an ``ensure`` shortfall."""
        if not self.share_prefix:
            return True
        pool = self.kv.pool
        ids = pool.owned(rid)
        if not ids:
            return True
        bs = self.kv.block_size
        first = start // bs
        last = min((max(end, start + 1) - 1) // bs, len(ids) - 1)
        for j in range(first, last + 1):
            if pool.ref_count(ids[j]) > 1:
                try:
                    old, new = pool.cow(rid, j)
                except MemoryError:
                    return False
                self.cache = self.kv.copy_pages(self.cache, old, new)
        return True

    def _restore_from_host(self, req: Request, slot: int,
                           entries: int) -> bool:
        """Re-admit an offloaded request by restoring its host-staged
        KV blocks into fresh pool pages; False = pool still too full."""
        n_blocks = self.offload_tier.stored_blocks(req.rid)
        if not self.kv.pool.can_alloc(n_blocks):
            return False                   # wait for blocks to free
        with self._admission(req, resume=True):
            host_leaves, n_blocks, nbytes, tax = \
                self.offload_tier.restore(req.rid)
            ids = self.kv.pool.alloc(req.rid, n_blocks)
            self.cache = self.kv.scatter_host(self.cache, ids, host_leaves)
        self.stats.restore_bytes += nbytes
        self.stats.offload_transfers += max(n_blocks, 1)
        self.stats.modeled_offload_tax_s += tax
        req._resume = None
        req.status = "active"
        req._admit_seq = self._admit_seq
        self._admit_seq += 1
        self.slots[slot] = req
        self.lengths[slot] = entries
        if self.tracer is not None:
            self.tracer.admit(req.rid, self.now, resume=True,
                              restore_bytes=nbytes, restore_tax_s=tax)
        if self.speculative:
            # the TARGET KV came back byte-exact from host memory, but the
            # draft cache was discarded at preemption: rebuild it from the
            # known token sequence (prompt + emitted minus the pending
            # last token — exactly ``entries`` tokens)
            self._draft_prefill_slot(
                slot, list(req.prompt) + list(req.generated[:-1]))
        return True

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Youngest decode-phase slot (latest admitted): it has the least
        sunk prefill/decode work to lose — vLLM's preemption order.  When
        every other slot is still prefilling, the youngest in-flight
        prefill is the last-resort victim (its partial KV is discarded,
        not offloaded — re-prefilling it is cheap)."""
        decode = [i for i, s in enumerate(self.slots)
                  if s is not None and i != exclude
                  and i not in self._prefill_tasks]
        if decode:
            return max(decode, key=lambda i: self.slots[i]._admit_seq)
        prefills = [i for i in self._prefill_tasks
                    if i != exclude and self.slots[i] is not None]
        if prefills:
            return max(prefills, key=lambda i: self.slots[i]._admit_seq)
        return None

    def _preempt(self, slot: int) -> None:
        """Evict a slot's request: offload its KV to host (or discard
        for recompute-on-resume) and free its blocks."""
        req = self.slots[slot]
        entries = int(self.lengths[slot])
        ids = self.kv.pool.owned(req.rid)
        mid_prefill = self._prefill_tasks.pop(slot, None) is not None
        nbytes, tax = 0, 0.0
        if self.offload_tier is not None and not mid_prefill:
            host = self.kv.gather_host(self.cache, ids)
            nbytes, tax = self.offload_tier.evict(req.rid, host, len(ids))
            self.stats.offload_bytes += nbytes
            self.stats.offload_transfers += max(len(ids), 1)
            self.stats.modeled_offload_tax_s += tax
            req._resume = ("host", entries)
        else:
            req._resume = ("recompute", None)
        if self.tracer is not None:
            self.tracer.preempt(req.rid, self.now, mode=req._resume[0],
                                offload_bytes=nbytes, offload_tax_s=tax)
        self._free_blocks(req.rid)
        self.slots[slot] = None
        self.lengths[slot] = 0
        req.status = "preempted"
        self._preempted.append(req)
        self.stats.preemptions += 1

    def _ensure_paged_blocks(self, req: Request, n_tokens: int,
                             exclude: int) -> bool:
        """Grow ``req`` to cover ``n_tokens`` KV entries, preempting
        youngest-first victims while the pool is short (evict-or-preempt).
        False = stalled: no victim available, caller retries next step."""
        pool = self.kv.pool
        while (pool.blocks_for(n_tokens) - len(pool.owned(req.rid))
               > pool.free_blocks):
            victim = self._pick_victim(exclude)
            if victim is None:
                return False
            self._preempt(victim)
        pool.ensure(req.rid, n_tokens)
        return True

    def _free_blocks(self, rid) -> None:
        """Return ``rid``'s blocks to the pool and zero their pages (one
        ``repro.release`` span)."""
        with profiler_span("release") as span:
            freed = self.kv.pool.free(rid)
            span.set_metadata(blocks=len(freed))
            self.cache = self.kv.zero_pages(self.cache, freed)

    def _release_slot(self, slot: int, req: Request) -> None:
        """Free a finished request's slot, blocks, and host staging."""
        self.slots[slot] = None
        self.lengths[slot] = 0
        self._free_blocks(req.rid)
        if self.offload_tier is not None:
            self.offload_tier.drop(req.rid)

    def _run_prefill_chunk(self, task: _PrefillTask, chunk_len: int) -> None:
        """Write the next ``chunk_len`` prompt tokens of one in-flight
        prefill into the paged cache (one backend call)."""
        with profiler_span("prefill_chunk", rid=task.req.rid,
                           tokens=chunk_len, start=task.pos):
            toks = np.asarray([task.toks[task.pos:task.pos + chunk_len]],
                              np.int32)
            bt = jnp.asarray(self.kv.table_row(task.req.rid))
            t0c = jnp.asarray(task.pos, jnp.int32)
            t_start = time.perf_counter()
            logits, self.cache = self.backend.prefill_chunk(
                self.cache, jnp.asarray(toks), bt, t0c)
        acct = self.backend.last
        self._absorb(acct, decode=False)
        task.last_logits = logits
        task.pos += chunk_len
        self.stats.prefill_chunks += 1
        dt = time.perf_counter() - t_start
        t_begin = self.now
        self.now += dt
        if self.tracer is not None:
            self.tracer.prefill(task.req.rid, t_begin, self.now,
                                tax_s=acct.host_time_s, replay=task.replay,
                                chunk=chunk_len)
        if self.telemetry is not None:
            self.telemetry.add(f"prefill_chunk[{chunk_len}]", "prefill",
                               t_begin, self.now, rid=task.req.rid,
                               slot=task.slot, pos=task.pos)
            self._record_segments(acct, t_begin)

    def _finish_prefill(self, task: _PrefillTask) -> None:
        """Complete a chunked prefill: emit the first token (or nothing
        on a replay) and move the slot into decode."""
        req, slot = task.req, task.slot
        del self._prefill_tasks[slot]
        self.lengths[slot] = len(task.toks)
        if self.share_prefix and len(task.toks) >= self.prefix_len:
            # the newest finished prefill becomes the freshest donor
            # candidate for its prefix key; its whole prompt is written
            self._register_donor(tuple(task.toks[:self.prefix_len]),
                                 req.rid, task.toks, len(task.toks))
        if task.replay:
            if self.speculative:
                self._draft_prefill_slot(slot, task.toks)
            return          # resumed recompute: KV rebuilt, nothing emitted
        first = self._sample(task.last_logits[0])
        req.generated.append(first)
        self.stats.prefills += 1
        self.stats.tokens_out += 1
        timing = self._note_first_token(req)
        if self.tracer is not None:
            self.tracer.first_token(req.rid, self.now)
        if len(req.generated) >= req.max_new_tokens:
            req.done = True
            req.status = "done"
            timing.done_s = self.now
            self._release_slot(slot, req)
            if self.tracer is not None:
                self.tracer.done(req.rid, self.now,
                                 n_tokens=len(req.generated))
        elif self.speculative:
            self._draft_prefill_slot(slot, task.toks)

    def _advance_prefills(self) -> bool:
        """One chunk of every in-flight prefill, interleaved with decode:
        a long prompt yields the engine back after each chunk instead of
        monopolizing it until its KV is fully built."""
        progressed = False
        for slot in sorted(self._prefill_tasks):
            task = self._prefill_tasks.get(slot)
            if task is None:        # finished earlier in this sweep
                continue
            remaining = len(task.toks) - task.pos
            chunk_len = (remaining if self.prefill_chunk is None
                         else min(self.prefill_chunk, remaining))
            with profiler_span("prepare"):
                # stalled on blocks or on a CoW copy block: retry next step
                ready = self._ensure_paged_blocks(
                    task.req, task.pos + chunk_len, exclude=slot) and \
                    self._cow_protect(task.req.rid, task.pos,
                                      task.pos + chunk_len)
            if not ready:
                continue
            self._run_prefill_chunk(task, chunk_len)
            progressed = True
            if task.pos >= len(task.toks):
                self._finish_prefill(task)
        return progressed

    def _paged_decode_step(self) -> bool:
        """One paged decode round: grow block tables (preempting if the
        pool is exhausted), step ready rows, advance chunked prefills.
        Returns False when nothing could progress."""
        with profiler_span("decode") as span:
            with profiler_span("prepare"):
                active = self._prepare_paged_rows()
                if not active:
                    return False
                toks = np.zeros((self.B, 1), np.int32)
                for i in active:
                    toks[i, 0] = self.slots[i].generated[-1]
                owners = [self.slots[i].rid
                          if self.slots[i] is not None
                          and i not in self._prefill_tasks else None
                          for i in range(self.B)]
                bt = jnp.asarray(self.kv.block_tables(owners))
                toks, lengths = jnp.asarray(toks), jnp.asarray(self.lengths)
            if profiling():
                held = self.lengths[active] + 1
                nb = self.kv.nb_per_slot
                span.set_metadata(
                    rows=len(active), live_tokens=int(held.sum()),
                    kv_pages=int(np.minimum(-(-held // self.kv.block_size),
                                            nb).sum()),
                    table_pages=self.B * nb)
            t0 = time.perf_counter()
            logits, self.cache = self.backend.paged_decode(
                self.cache, toks, lengths, bt)
            acct = self.backend.last
            self._absorb(acct, decode=True)
            self.stats.decode_steps += 1
            self.stats.slot_occupancy.append(len(active))
            self.stats.block_pool_utilization.append(
                self.kv.pool.utilization)
            with profiler_span("sync", what="logits"):
                logits_np = np.asarray(logits)
            dt = time.perf_counter() - t0
            t_begin = self.now
            self.now += dt
            self.stats.step_times_s.append(dt)
            self._note_step(len(active), dt, acct)
            if self.tracer is not None:
                self.tracer.decode([self.slots[i].rid for i in active],
                                   t_begin, self.now, tax_s=acct.host_time_s,
                                   batch=len(active),
                                   modeled_tklqt_s=acct.modeled_tklqt_s)
            if self.telemetry is not None:
                self.telemetry.add(f"decode[b={len(active)}]", "decode",
                                   t_begin, self.now, batch=len(active))
                self._record_segments(acct, t_begin)
            for i in active:
                req = self.slots[i]
                self.lengths[i] += 1
                nxt = int(np.argmax(logits_np[i]))
                req.generated.append(nxt)
                self.stats.tokens_out += 1
                timing = self.timings.get(req.rid)
                self._note_token(timing)
                if len(req.generated) >= req.max_new_tokens or \
                        self.lengths[i] >= self.T - 1:
                    req.done = True
                    req.status = "done"
                    if timing is not None:
                        timing.done_s = self.now
                    self._release_slot(i, req)
                    if self.tracer is not None:
                        self.tracer.done(req.rid, self.now,
                                         n_tokens=len(req.generated))
        return True

    def _prepare_paged_rows(self) -> list:
        """Grow every decode row's table to cover the entry this step
        writes; growth may preempt younger rows out of this very step.
        Returns the rows that can step."""
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and i not in self._prefill_tasks]
        stalled = set()
        for i in active:
            if self.slots[i] is None:
                continue
            if not self._ensure_paged_blocks(
                    self.slots[i], int(self.lengths[i]) + 1, exclude=i):
                # no victim right now (in-flight prefills hold the rest):
                # sit this step out — a finishing prefill frees blocks or
                # becomes preemptable next step.  A true deadlock (nothing
                # anywhere can progress) is raised by run().
                stalled.add(i)
            elif not self._cow_protect(self.slots[i].rid,
                                       int(self.lengths[i]),
                                       int(self.lengths[i]) + 1):
                stalled.add(i)
        return [i for i in active
                if self.slots[i] is not None and i not in stalled]

    # ------------------------------------------------------------ speculative
    def _draft_prefill_slot(self, slot: int, toks_list) -> None:
        """Build the draft's KV for a slot from the known token sequence
        (bucketed like target prefill; the body zeroes the slot row)."""
        plen = len(toks_list)
        bucket = self._bucket(plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = toks_list
        _, self.draft_cache = self.backend.draft_prefill(
            self.draft_cache, jnp.asarray(toks), slot, plen)
        self._absorb(self.backend.last, decode=False)
        self.draft_lengths[slot] = plen

    def _spec_depth(self) -> int:
        """Launch-tax-aware k for this round: deep while the measured
        boundedness says decode is CPU/dispatch-bound at the current
        batch, shallow near the inflection, 0 (plain decode) past it."""
        batch = sum(1 for i, s in enumerate(self.slots)
                    if s is not None and i not in self._prefill_tasks)
        return pick_spec_k(batch, max_k=self.spec_k,
                           inflection_batch=self.spec_inflection)

    def _spec_round(self, k: int, paged: bool) -> bool:
        """One draft-propose / batched-verify round for all decode-ready
        slots.  The draft proposes k tokens autoregressively (k launches on
        its own dispatch stream), the target verifies all k+1 positions in
        ONE batched forward, and the longest draft prefix matching target
        argmax is emitted plus the target's correction token — so every
        emitted token is a target argmax from the true prefix and the
        output stays byte-identical to plain greedy decode."""
        if paged:
            active = [i for i, s in enumerate(self.slots)
                      if s is not None and i not in self._prefill_tasks]
            # grow every row's table to cover the whole verify window
            # (L .. L+k); growth may preempt younger rows out of this round
            stalled = set()
            for i in active:
                if self.slots[i] is None:
                    continue
                want = min(int(self.lengths[i]) + k + 1, self.T)
                if not self._ensure_paged_blocks(self.slots[i], want,
                                                 exclude=i):
                    stalled.add(i)
                elif not self._cow_protect(self.slots[i].rid,
                                           int(self.lengths[i]), want):
                    stalled.add(i)
            active = [i for i in active
                      if self.slots[i] is not None and i not in stalled]
        else:
            active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return False
        t0 = time.perf_counter()
        # --- draft propose: one width-2 right-aligned catch-up step (the
        # draft never saw its own k-th proposal after a fully-accepted
        # window, so it may be one token behind), then k-1 single steps.
        # Padding columns carry position T: the cache write drops and the
        # logits column is ignored.
        cat_toks = np.zeros((self.B, 2), np.int32)
        cat_pos = np.full((self.B, 2), self.T, np.int32)
        for i in active:
            req = self.slots[i]
            L = int(self.lengths[i])
            cat_toks[i, 1] = req.generated[-1]
            cat_pos[i, 1] = L
            if int(self.draft_lengths[i]) == L - 1:
                cat_toks[i, 0] = req.generated[-2]
                cat_pos[i, 0] = L - 1
        draft = np.zeros((self.B, k), np.int64)
        logits_d, self.draft_cache = self.backend.draft_step(
            self.draft_cache, jnp.asarray(cat_toks), jnp.asarray(cat_pos),
            jnp.asarray(self.draft_lengths))
        self._absorb(self.backend.last, decode=True)
        draft[:, 0] = np.argmax(np.asarray(logits_d), axis=-1)
        for i in active:
            self.draft_lengths[i] = int(self.lengths[i]) + 1
        for j in range(1, k):
            toks_j = np.zeros((self.B, 1), np.int32)
            pos_j = np.full((self.B, 1), self.T, np.int32)
            for i in active:
                toks_j[i, 0] = draft[i, j - 1]
                pos_j[i, 0] = self.draft_lengths[i]
            logits_d, self.draft_cache = self.backend.draft_step(
                self.draft_cache, jnp.asarray(toks_j), jnp.asarray(pos_j),
                jnp.asarray(self.draft_lengths))
            self._absorb(self.backend.last, decode=True)
            draft[:, j] = np.argmax(np.asarray(logits_d), axis=-1)
            for i in active:
                self.draft_lengths[i] += 1
        # --- batched verify: the target scores all k+1 positions at once
        ver = np.zeros((self.B, k + 1), np.int32)
        for i in active:
            ver[i, 0] = self.slots[i].generated[-1]
            ver[i, 1:] = draft[i]
        lengths = jnp.asarray(self.lengths)
        if paged:
            owners = [self.slots[i].rid if self.slots[i] is not None
                      and i not in self._prefill_tasks else None
                      for i in range(self.B)]
            bt = jnp.asarray(self.kv.block_tables(owners))
            logits, self.cache = self.backend.paged_verify(
                self.cache, jnp.asarray(ver), lengths, bt)
        else:
            logits, self.cache = self.backend.verify(
                self.cache, jnp.asarray(ver), lengths)
        acct = self.backend.last
        acct.proposed = k * len(active)
        tgt = np.argmax(np.asarray(logits), axis=-1)    # (B, k+1)
        dt = time.perf_counter() - t0
        t_begin = self.now
        self.now += dt
        self.stats.step_times_s.append(dt)
        self._note_step(len(active), dt, acct)
        self.stats.decode_steps += 1
        self.stats.spec_rounds += 1
        self.stats.slot_occupancy.append(len(active))
        if paged:
            self.stats.block_pool_utilization.append(
                self.kv.pool.utilization)
        if self.tracer is not None:
            # one interval covering the whole draft-propose + verify round
            self.tracer.decode([self.slots[i].rid for i in active],
                               t_begin, self.now, tax_s=acct.host_time_s,
                               batch=len(active),
                               modeled_tklqt_s=acct.modeled_tklqt_s)
        if self.telemetry is not None:
            self.telemetry.add(f"spec_verify[b={len(active)},k={k}]",
                               "decode", t_begin, self.now,
                               batch=len(active))
        total_accepted = 0
        for i in active:
            req = self.slots[i]
            L = int(self.lengths[i])
            n_acc = 0
            while n_acc < k and int(draft[i, n_acc]) == int(tgt[i, n_acc]):
                n_acc += 1
            # emit the accepted prefix + the target's correction token,
            # respecting the same budget/length stops as plain decode
            timing = self.timings.get(req.rid)
            Lcur = L
            for j in range(n_acc + 1):
                req.generated.append(int(tgt[i, j]))
                Lcur += 1
                self.stats.tokens_out += 1
                if j < n_acc:
                    total_accepted += 1
                else:
                    self.stats.corrections += 1
                self._note_token(timing)
                if len(req.generated) >= req.max_new_tokens or \
                        Lcur >= self.T - 1:
                    req.done = True
                    break
            self.lengths[i] = Lcur
            # draft rollback is just a length retreat: entries past the
            # accepted prefix are stale, masked by kv_valid until the next
            # window overwrites them
            self.draft_lengths[i] = L + min(n_acc + 1, k)
            if req.done:
                req.status = "done"
                if timing is not None:
                    timing.done_s = self.now
                if self.tracer is not None:
                    self.tracer.done(req.rid, self.now,
                                     n_tokens=len(req.generated))
                if paged:
                    self._release_slot(i, req)
                else:
                    self.slots[i] = None
                    self.lengths[i] = 0
            elif paged:
                # block-table rollback: free + zero the tail blocks grown
                # for rejected verify positions
                freed = self.kv.pool.trim(req.rid, Lcur)
                if freed:
                    self.cache = self.kv.zero_pages(self.cache, freed)
        acct.accepted = total_accepted
        self._absorb(acct, decode=True)
        return True

    def step(self):
        """One decode step for all active slots."""
        if self.cache_mode == "paged":
            progressed = self._advance_prefills()
            k = self._spec_depth() if self.speculative else 0
            if k:
                progressed = self._spec_round(k, paged=True) or progressed
            else:
                progressed = self._paged_decode_step() or progressed
            self._last_step_progressed = progressed
            return
        if self.speculative:
            k = self._spec_depth()
            if k:
                self._spec_round(k, paged=False)
                return
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return
        with profiler_span("decode", rows=len(active)):
            toks = np.zeros((self.B, 1), np.int32)
            for i in active:
                toks[i, 0] = self.slots[i].generated[-1]
            t0 = time.perf_counter()
            logits, self.cache = self.backend.decode(
                self.cache, jnp.asarray(toks), jnp.asarray(self.lengths))
            acct = self.backend.last
            self._absorb(acct, decode=True)
            self.stats.decode_steps += 1
            self.stats.slot_occupancy.append(len(active))
            with profiler_span("sync", what="logits"):
                logits_np = np.asarray(logits)
        dt = time.perf_counter() - t0
        t_begin = self.now
        self.now += dt
        self.stats.step_times_s.append(dt)
        self._note_step(len(active), dt, acct)
        if self.tracer is not None:
            self.tracer.decode([self.slots[i].rid for i in active],
                               t_begin, self.now, tax_s=acct.host_time_s,
                               batch=len(active),
                               modeled_tklqt_s=acct.modeled_tklqt_s)
        if self.telemetry is not None:
            self.telemetry.add(f"decode[b={len(active)}]", "decode",
                               t_begin, self.now, batch=len(active))
            self._record_segments(acct, t_begin)
        for i in active:
            req = self.slots[i]
            self.lengths[i] += 1
            nxt = int(np.argmax(logits_np[i]))
            req.generated.append(nxt)
            self.stats.tokens_out += 1
            timing = self.timings.get(req.rid)
            self._note_token(timing)
            if len(req.generated) >= req.max_new_tokens or \
                    self.lengths[i] >= self.T - 1:
                req.done = True
                req.status = "done"
                self.slots[i] = None
                self.lengths[i] = 0
                if timing is not None:
                    timing.done_s = self.now
                if self.tracer is not None:
                    self.tracer.done(req.rid, self.now,
                                     n_tokens=len(req.generated))

    # ------------------------------------------------------------ run loop
    def submit(self, req: Request) -> None:
        """Enqueue one request for admission (open-loop ingress).

        The engine holds it until the virtual clock reaches
        ``req.arrival_s`` AND a slot frees; ``tick()`` drains the queue.
        This is the entry point an external router uses to feed a replica
        incrementally — ``run()`` is submit-everything-then-drain.
        """
        req._t_submit = time.perf_counter()
        if self.tracer is not None:
            # idempotent: a router-fed replica already minted this trace
            # at fleet ingress; engine-only runs mint it here
            self.tracer.ingress(req.rid, req.arrival_s)
        self._pending.append(req)
        # stable sort: equal arrival times keep submission order, so a
        # router-fed replica admits exactly like run() over the same list
        self._pending.sort(key=lambda r: r.arrival_s)

    @property
    def busy(self) -> bool:
        """True while any work remains: queued, preempted, or in a slot."""
        return bool(self._pending) or bool(self._preempted) or \
            any(s is not None for s in self.slots)

    @property
    def queue_depth(self) -> int:
        """Requests admitted-or-waiting on this engine (pending +
        preempted + active slots) — the router's load signal."""
        return (len(self._pending) + len(self._preempted)
                + sum(1 for s in self.slots if s is not None))

    @property
    def outstanding_tokens(self) -> int:
        """Un-served work in tokens: full prompt+budget for queued
        requests, remaining decode budget for admitted ones.  Routing
        tie-breaker — two replicas with equal request counts can hold
        very different amounts of work."""
        n = sum(len(r.prompt) + r.max_new_tokens for r in self._pending)
        n += sum(r.max_new_tokens - len(r.generated)
                 for r in self._preempted)
        n += sum(s.max_new_tokens - len(s.generated)
                 for s in self.slots if s is not None)
        return n

    def tick(self) -> bool:
        """One scheduling round: fast-forward over idle gaps, admit every
        eligible request (resumed ones first — they hold generation
        progress and possibly offloaded KV), then one ``step()``.

        Returns False (doing nothing) once no work remains.  ``run()`` is
        a tick loop; a fleet router interleaves ticks of many replicas on
        one global clock.
        """
        if not self.busy:
            return False
        self._ticks += 1
        with jax.profiler.StepTraceAnnotation("repro.tick",
                                              step_num=self._ticks) as span:
            traced = profiling()
            if traced:
                chunks, steps = self.stats.prefill_chunks, \
                    self.stats.decode_steps
            admitted = self._tick()
            self._hand_over_first_tokens()
            if traced:
                span.set_metadata(
                    decode_rows=(self.stats.slot_occupancy[-1]
                                 if self.stats.decode_steps > steps else 0),
                    prefill_chunks=self.stats.prefill_chunks - chunks,
                    admitted=admitted)
        return True

    def _tick(self) -> int:
        """The body of ``tick()``; returns the number of admissions."""
        idle = not any(s is not None for s in self.slots) \
            and not self._preempted
        if idle and self._pending and \
                self._pending[0].arrival_s > self.now:
            self.now = self._pending[0].arrival_s
        admitted = 0
        # resumed requests first: they hold generation progress (and
        # possibly offloaded KV) — finishing them frees blocks fastest
        while self._preempted and self._free_slot() is not None:
            if not self._admit_paged(self._preempted[0]):
                break               # no blocks to restore into yet
            self._preempted.pop(0)
            admitted += 1
        while (self._pending and self._pending[0].arrival_s <= self.now
               and self._free_slot() is not None):
            if self.admit(self._pending[0]):
                self._pending.pop(0)
                admitted += 1
            else:
                break
        self.step()
        if self.cache_mode == "paged" and not admitted \
                and not self._last_step_progressed \
                and (self._preempted
                     or any(s is not None for s in self.slots)):
            # nothing ran and nothing was admitted: no future step can
            # free blocks either — the pool cannot hold this workload
            raise RuntimeError(
                "paged engine deadlocked: block pool "
                f"({self.kv.num_blocks} x {self.kv.block_size} tokens) "
                "too small for even one in-flight request; raise "
                "num_blocks")
        return admitted

    def run(self, requests: list[Request]) -> list[Request]:
        """Continuous batching: admit whenever a slot frees.

        Requests with ``arrival_s > 0`` are held until the engine clock
        reaches them (open-loop traffic).  When every slot is idle and the
        next arrival is in the future, the clock fast-forwards to it — the
        idle gap is honored on the virtual timeline without wall-time cost.
        """
        for r in sorted(requests, key=lambda r: r.arrival_s):
            self.submit(r)
        done: list[Request] = []
        while self.tick():
            for r in requests:
                if r.done and r not in done:
                    done.append(r)
        for r in requests:
            if r.done and r not in done:
                done.append(r)
        return done

    def reset(self):
        """Clear serving state (slots, stats, clock, timings) but keep the
        compiled/planned functions — warmup run, reset, measured run."""
        self.cache = jax.tree.map(jnp.zeros_like, self.cache)
        self.lengths = np.zeros(self.B, np.int32)
        self.slots = [None] * self.B
        # fresh registry so the measured run's gauges/histograms don't
        # carry warmup observations; everything instrumented rebinds below
        self.registry = MetricsRegistry()
        self.stats = EngineStats(plan=self.plan_label,
                                 tp=self.backend.info.tp,
                                 registry=self.registry)
        self._dev_base = self.backend.device_dispatches
        self.now = 0.0
        if self.monitor is not None:
            self.monitor.clear()
        if self.speculative:
            self.draft_cache = jax.tree.map(jnp.zeros_like, self.draft_cache)
            self.draft_lengths = np.zeros(self.B, np.int32)
        self._pending = []
        self._first_tokens = []
        if self.cache_mode == "paged":
            self.kv.reset()
            self._prefill_tasks = {}
            self._preempted = []
            self._admit_seq = 0
            self._prefix_donors = {}
            if self.offload_tier is not None:
                self.offload_tier.clear()
        if self.telemetry is not None:
            self.telemetry.clear()
        self._bind_telemetry()
