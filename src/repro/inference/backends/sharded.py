"""Tensor-parallel sharded execution backend (Megatron-style, shard_map).

Params are sharded over a ``(data=1, model=tp)`` host mesh with the
existing sharding-rule engine (``distributed.sharding.param_specs`` /
``cache_specs`` / ``paged_cache_specs`` + ``launch.mesh.make_host_mesh``):
wq/wk/wv column-sharded by head, wo row-sharded, MLP d_ff split, KV caches
(contiguous and paged) head-sharded.  The prefill/decode bodies run under
``jax.shard_map`` with a PER-DEVICE config — ``n_heads/tp`` local heads —
and the model's ``reduce`` hook psums the partial attention/MLP outputs
over the model axis.  Embeddings and the LM head stay replicated, so every device holds
identical activations between blocks and the greedy tokens are the same
ones the single-device ``LocalBackend`` emits.

Accounting is the point: each step is ONE executable but ``tp`` device
dispatch streams (``CallAccount.dispatches = tp`` — the per-device launch
multiplication of Chung et al.), and every psum the body issues is
captured AT TRACE TIME (name + payload bytes) then priced over the
platform's coupling link via ``core.device_model.allreduce_cost_s`` — the
LC/PCIe vs CC/NVLink-C2C axis applied to tensor-parallel serving.

Runs on CPU CI: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
simulates the device pool (``make_host_mesh`` validates and says exactly
that when devices are short).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.device_model import PLATFORMS, allreduce_cost_s
from repro.distributed.sharding import (cache_specs, paged_cache_specs,
                                        param_specs, shardings_for)
from repro.inference.backends.base import (AccountingMixin, BackendInfo,
                                           CallAccount)
from repro.inference.backends.bodies import make_step_bodies
from repro.launch.mesh import make_host_mesh
from repro.models import init_params, make_cache
from repro.telemetry.spans import profiler_span

_SUPPORTED_KINDS = ("attn", "attn_local")


def _validate_tp(cfg: ModelConfig, tp: int) -> None:
    """Reject configs the head-sharded shard_map body cannot serve."""
    if tp < 2:
        raise ValueError(f"ShardedBackend needs tp >= 2, got {tp} "
                         "(use LocalBackend for single-device serving)")
    bad = [k for k in cfg.block_pattern if k not in _SUPPORTED_KINDS]
    if bad or cfg.moe_slots or cfg.n_encoder_layers:
        raise ValueError(
            f"ShardedBackend supports pure-attention decoder stacks; "
            f"{cfg.name} has block kinds {bad or cfg.block_pattern}, "
            f"moe_slots={cfg.moe_slots}, "
            f"n_encoder_layers={cfg.n_encoder_layers}")
    for dim, val in (("n_heads", cfg.n_heads),
                     ("n_kv_heads", cfg.n_kv_heads),
                     ("d_ff", cfg.d_ff)):
        if val % tp:
            raise ValueError(
                f"tp={tp} must divide {dim}={val} for {cfg.name}: the "
                f"shard_map body runs {dim}//tp per device (pick a tp "
                f"from the divisors of {val}, or serve this arch with "
                f"tp=1)")


def _param_spec_tree(params, cfg: ModelConfig, mesh):
    """Head-sharded param specs with embeddings + unembed replicated:
    every device computes the full (tiny at decode) logits row, so
    out_specs need no gather.  ``params`` may be arrays or shapes."""
    specs = dict(param_specs(params, cfg, mesh, tp="model"))
    specs["embed"] = P(None, None)
    if "lm_head" in specs:
        specs["lm_head"] = P(None, None)
    return specs


def _jit_into(make, spec_tree, mesh):
    """Run ``make()`` under jit straight into ``spec_tree``'s shardings:
    each device materializes only its own shards, so a pytree larger
    than one device never sits whole on one device."""
    shardings = shardings_for(jax.eval_shape(make), spec_tree, mesh)
    return jax.jit(make, out_shardings=shardings)()


def init_sharded_params(key, cfg: ModelConfig, tp: int):
    """``init_params(key, cfg)`` placed in ``ShardedBackend``'s layout
    over the first ``tp`` devices, never whole on one of them — the way
    to build a model that only fits across the mesh."""
    _validate_tp(cfg, tp)
    mesh = make_host_mesh(data=1, model=tp)

    def make():
        return init_params(key, cfg)

    specs = _param_spec_tree(jax.eval_shape(make), cfg, mesh)
    return _jit_into(make, specs, mesh)


class ShardedBackend(AccountingMixin):
    """Head-sharded tensor-parallel backend over a host/device mesh."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int,
                 max_len: int, tp: int, platform: str = "TPU-v5e",
                 plan: str = "jit"):
        if plan != "jit":
            raise ValueError(
                f"ShardedBackend executes plan='jit' only (got {plan!r}): "
                "the launch-plan runtime replays single-device kernel "
                "streams and cannot re-dispatch shard_map bodies; "
                "per-device launch pricing for tp>1 comes from "
                "Planner(tp=...) / telemetry.characterize.tp_sweep")
        _validate_tp(cfg, tp)
        self.cfg = cfg
        self.tp = tp
        self.B = max_batch
        self.T = max_len
        self.plan = plan
        self.platform = platform
        self.spec = PLATFORMS[platform]
        # raises the actionable device-count error when the pool is short
        self.mesh = make_host_mesh(data=1, model=tp)
        self.info = BackendInfo(
            kind="sharded", tp=tp,
            devices=tuple(d.id for d in self.mesh.devices.flat))
        self._init_accounting()
        # per-device view: the body reshapes local projections with LOCAL
        # head counts (head_dim pinned — d_model//n_heads_local is wrong)
        self.cfg_local = cfg.replace(n_heads=cfg.n_heads // tp,
                                     n_kv_heads=cfg.n_kv_heads // tp,
                                     head_dim=cfg.hd)
        specs = _param_spec_tree(params, cfg, self.mesh)
        self.param_spec_tree = specs
        # a no-op for params from ``init_sharded_params``
        self.params = jax.device_put(
            params, shardings_for(params, specs, self.mesh))
        self._cache_spec_tree = None        # set by init_*_cache
        self._fns: dict = {}                # key -> jitted shard_map fn
        self._profiles: dict = {}           # key -> ((name, bytes), ...)
        self._trace_log: list = []          # filled by reduce() at trace time

        def reduce(name, x):
            # trace-time capture: one entry per psum ISSUED IN THE TRACED
            # BODY (the superblock scan body traces once — scale by
            # n_superblocks at accounting time); x.shape is the local
            # (per-device) payload entering the collective
            self._trace_log.append(
                (name, int(x.size) * x.dtype.itemsize))
            return jax.lax.psum(x, "model")

        self._reduce = reduce
        # IDENTICAL numerics to LocalBackend (bodies.py), instantiated
        # with the per-device config + psum hook — the byte-identical
        # tokens guarantee is structural, not hand-synchronized
        bodies = make_step_bodies(self.cfg_local, reduce=reduce)
        self._prefill_body = bodies.prefill
        self._decode_body = bodies.decode
        self._paged_prefill_body = bodies.paged_prefill
        self._paged_decode_body = bodies.paged_decode
        self._verify_body = bodies.verify
        self._paged_verify_body = bodies.paged_verify

    # ------------------------------------------------------------ caches
    def init_contiguous_cache(self):
        """Head-sharded contiguous KV cache built on the tp mesh."""
        def make():
            return make_cache(self.cfg, self.B, self.T, src_len=1,
                              dtype=self.cfg.cdtype)

        self._cache_spec_tree = cache_specs(
            jax.eval_shape(make), self.cfg, self.mesh, dp=("data",),
            tp="model")
        return _jit_into(make, self._cache_spec_tree, self.mesh)

    def init_paged_cache(self, kv):
        """Pooled KV pages in tp whole-head groups, one group per device
        of the tp mesh."""
        def make():
            return kv.make_pages(groups=self.tp)

        self._cache_spec_tree = paged_cache_specs(
            jax.eval_shape(make), self.cfg, self.mesh, tp="model")
        return _jit_into(make, self._cache_spec_tree, self.mesh)

    # ------------------------------------------------------------ dispatch
    def _wrapped(self, key, body, arg_specs, logits_spec=P(None, None)):
        """jit(jax.shard_map(body)) for one step kind, built lazily once the
        cache spec tree exists (cache structure fixes in_specs)."""
        fn = self._fns.get(key)
        if fn is None:
            if self._cache_spec_tree is None:
                raise RuntimeError(
                    "backend cache not initialized; call "
                    "init_contiguous_cache()/init_paged_cache() first")
            in_specs = (self.param_spec_tree, self._cache_spec_tree,
                        *arg_specs)
            out_specs = (logits_spec, self._cache_spec_tree)
            fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False))
            self._fns[key] = fn
        return fn

    def _call(self, key, fn, args, launch: str):
        """Invoke one sharded step (in a ``repro.launch.<launch>`` span)
        and charge tp dispatch streams plus the psum traffic captured at
        trace time (priced per platform)."""
        mark = len(self._trace_log)
        t0 = time.perf_counter()
        with profiler_span("launch." + launch):
            logits, cache = fn(self.params, *args)
        host = time.perf_counter() - t0
        new = self._trace_log[mark:]
        del self._trace_log[mark:]
        if new:
            self._profiles[key] = tuple(new)
        prof = self._profiles.get(key, ())
        # the superblock scan body traces once but runs n_superblocks
        # times: every captured psum fires once per superblock
        n_sb = self.cfg.n_superblocks
        payload = sum(b for _, b in prof) * n_sb
        tax = n_sb * sum(allreduce_cost_s(self.spec, b, self.tp)
                         for _, b in prof)
        self._charge(CallAccount(
            dispatches=self.tp, host_time_s=host,
            collectives=len(prof) * n_sb, collective_bytes=payload,
            modeled_collective_tax_s=tax))
        return logits, cache

    # ------------------------------------------------------------ steps
    def prefill(self, cache, tokens, slot: int, plen: int):
        """Sharded prompt prefill into a contiguous-cache slot."""
        key = ("prefill", tokens.shape[1], plen)
        fn = self._fns.get(key)
        if fn is None:
            def body(params, cache, tokens, slot):
                return self._prefill_body(params, cache, tokens, slot, plen)
            fn = self._wrapped(key, body, (P(None, None), P()))
        return self._call(key, fn, (cache, tokens,
                                    jnp.asarray(slot, jnp.int32)),
                          "prefill_body")

    def decode(self, cache, tokens, lengths):
        """One sharded batched decode step (contiguous cache)."""
        key = ("decode",)
        fn = self._fns.get(key) or self._wrapped(
            key, self._decode_body, (P(None, None), P(None)))
        return self._call(key, fn, (cache, tokens, lengths), "decode_body")

    def prefill_chunk(self, cache, tokens, bt_row, t0_index):
        """Sharded paged prompt-chunk write through a block table."""
        key = ("prefill_chunk", tokens.shape[1])
        fn = self._fns.get(key) or self._wrapped(
            key, self._paged_prefill_body, (P(None, None), P(None), P()))
        return self._call(key, fn, (cache, tokens, bt_row, t0_index),
                          "prefill_chunk")

    def paged_decode(self, cache, tokens, lengths, block_tables):
        """One sharded batched decode step over paged KV."""
        key = ("paged_decode",)
        fn = self._fns.get(key) or self._wrapped(
            key, self._paged_decode_body,
            (P(None, None), P(None), P(None, None)))
        return self._call(key, fn, (cache, tokens, lengths, block_tables),
                          "decode")

    def verify(self, cache, tokens, lengths):
        """Sharded speculative verify (k+1 positions, one forward)."""
        # speculative verify composes with tp: same shard_map body family,
        # replicated (B, k+1, V) logits out (tiny at decode widths)
        key = ("verify",)
        fn = self._fns.get(key) or self._wrapped(
            key, self._verify_body, (P(None, None), P(None)),
            logits_spec=P(None, None, None))
        return self._call(key, fn, (cache, tokens, lengths), "verify_body")

    def paged_verify(self, cache, tokens, lengths, block_tables):
        """Paged-cache variant of sharded ``verify``."""
        key = ("paged_verify",)
        fn = self._fns.get(key) or self._wrapped(
            key, self._paged_verify_body,
            (P(None, None), P(None), P(None, None)),
            logits_spec=P(None, None, None))
        return self._call(key, fn, (cache, tokens, lengths, block_tables),
                          "paged_verify_body")

    # ------------------------------------------------------- accounting
    @property
    def planned_decode(self):
        """Launch-plan decode handle — always None (jit-only backend)."""
        return None
