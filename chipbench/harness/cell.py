"""One run of one cell: set-up, warm-up, lead-in, window, drain.

The engine is the program's own ``ServeEngine`` with the arguments
``launch/serve.py`` gives it (plan ``jit``, the boundedness monitor on,
paged bfloat16 cache), sized by the configuration file.  Set-up builds
the weights on the device from the seed, warms every shape the cell's
traffic uses (each prefill-chunk length its prompts can produce, the
decode step, and the page zeroing for every count of freed blocks), then
resets the engine and starts the traffic.  The window opens after the
mix's lead-in; everything before it is set-up.
"""
from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass, field

from chipbench.harness import traffic, work
from chipbench.harness.driver import Driver, annotation

# HF config.json keys -> repro ModelConfig fields
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
           "qkv_bias": "qkv_bias", "rms_norm_eps": "norm_eps"}


@dataclass
class Run:
    """What a run leaves for the metric readers."""
    cell: str
    model: dict
    mix: dict
    chips: int
    peaks: dict
    setup_s: float = math.nan
    open: float = math.nan           # window, host perf_counter seconds
    close: float = math.nan
    drain_end: float = math.nan
    reqs: list = field(default_factory=list)
    lateness: list = field(default_factory=list)
    compiles_in_window: int = 0
    phases: dict = field(default_factory=dict)     # set-up, seconds

    def in_window(self, t: float) -> bool:
        return self.open <= t < self.close

    def due_in_window(self) -> list:
        return [r for r in self.reqs if self.in_window(r.due_abs)]


def repro_config(model: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry with every size the file states."""
    from repro.configs import get_config
    kw = {f: model[k] for k, f in _FIELDS.items() if k in model}
    kw["param_dtype"] = kw["compute_dtype"] = model["torch_dtype"]
    return get_config(model["registry"]).replace(**kw)


def build_engine(model: dict, seed: int):
    from repro.inference.engine import ServeEngine
    from chipbench.harness.weights import program_params
    cfg = repro_config(model)
    sv = model["serving"]
    params = program_params(cfg, seed)
    return ServeEngine(cfg, params, max_batch=sv["max_batch"],
                       max_len=sv["max_len"], plan="jit",
                       cache="paged", block_size=sv["block_size"],
                       num_blocks=sv.get("num_blocks"),
                       prefill_chunk=sv["prefill_chunk"],
                       kv_dtype=sv.get("kv_dtype", "bf16"))


def chunk_lengths(mix: dict, chunk: int) -> list[int]:
    """Every prefill-chunk length the mix's prompts can produce."""
    step = mix.get("round_prompt_to", 1)
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    seen = set()
    for n in range(-(-lo // step) * step, hi + 1, step):
        if n >= chunk:
            seen.add(chunk)
        if n % chunk:
            seen.add(n % chunk)
    return sorted(seen)


def warm_up(eng, mix: dict, seed: int) -> None:
    """Compile or load every program the window will run, then reset."""
    from repro.inference.engine import Request
    vocab = eng.cfg.vocab_size
    for i, c in enumerate(chunk_lengths(mix, eng.prefill_chunk)):
        eng.submit(Request(-1 - i, prompt=traffic.prompt_tokens(
            seed, 2**31 + i, c, vocab), max_new_tokens=2))
    while eng.tick():
        pass
    # a finished request's pages are zeroed with one program per count of
    # freed blocks; every count up to a full slot can occur
    for n in range(1, eng.kv.nb_per_slot + 1):
        eng.cache = eng.kv.zero_pages(eng.cache, list(range(n)))
    eng.reset()


def instrument(eng, model: dict) -> None:
    """Wrap the engine's calls into its backend and page cache with host
    annotations that carry each call's work, for the traced run."""
    be, kv, m = eng.backend, eng.kv, work.Dims.of(model)

    decode, chunk, zero = be.paged_decode, be.prefill_chunk, kv.zero_pages

    def paged_decode(cache, tokens, lengths, tables):
        live = [int(eng.lengths[i]) + 1 for i, r in enumerate(eng.slots)
                if r is not None and r.generated]
        af, ab = work.attention(m, live)
        with annotation("decode", rows=len(live), attn_flops=af,
                        attn_bytes=ab,
                        model_flops=work.decode_flops(m, live)):
            return decode(cache, tokens, lengths, tables)

    def prefill_chunk(cache, tokens, bt_row, t0_index):
        c, t0 = int(tokens.shape[1]), int(t0_index)
        af, ab = work.attention_chunk(m, t0, c)
        with annotation("prefill_chunk", tokens=c, attn_flops=af,
                        attn_bytes=ab,
                        model_flops=work.chunk_flops(m, t0, c)):
            return chunk(cache, tokens, bt_row, t0_index)

    def zero_pages(pages, ids):
        with annotation("zero_pages", blocks=len(ids)):
            return zero(pages, ids)

    be.paged_decode, be.prefill_chunk = paged_decode, prefill_chunk
    kv.zero_pages = zero_pages


class CompileCounter:
    """Counts compiles and persistent-cache loads while ``active``."""

    def __init__(self):
        import jax
        self.active, self.n = False, 0

        def on_duration(event, duration, **kw):
            if self.active and event.endswith("backend_compile_duration"):
                self.n += 1

        def on_event(event, **kw):
            if self.active and event.endswith("compilation_cache/cache_hits"):
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def execute(run: Run, seed: int, seconds: float, t_process: float,
            trace_dir=None, trace_seconds: float = 10.0):
    """Set up, drive and drain one run; returns the requests' records with
    the engine freed.  ``trace_dir`` traces the first ``trace_seconds``
    of the window."""
    import jax
    counter = CompileCounter()
    t = time.perf_counter()
    run.phases["before_engine"] = t - t_process
    eng = build_engine(run.model, seed)
    run.phases["weights_and_engine"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_up(eng, run.mix, seed)
    run.phases["warm_up"] = time.perf_counter() - t
    if trace_dir is not None:
        instrument(eng, run.model)
    reqs = traffic.generate(run.mix, seed, seconds)
    if any(r.prompt_len + r.max_new > eng.T for r in reqs):
        raise ValueError(f"{run.mix['name']}: a request is longer than the "
                         f"engine's {eng.T} tokens; set max_total")
    drv = Driver(eng, reqs, seed, eng.cfg.vocab_size,
                 annotate=trace_dir is not None)
    drv.start = time.perf_counter()
    run.open = drv.start + run.mix.get("lead_in_s", 0.0)
    run.close = run.open + seconds
    drv.run_until(run.open)
    run.setup_s = time.perf_counter() - t_process
    run.phases["lead_in"] = run.open - drv.start
    counter.active = True
    if trace_dir is not None:
        trace_end = run.open + min(seconds, trace_seconds)
        jax.profiler.start_trace(str(trace_dir))
        with annotation("window"):
            drv.run_until(trace_end)
        jax.profiler.stop_trace()
    drv.run_until(run.close)
    counter.active = False
    due = [r for r in drv.sent if run.in_window(r.due_abs)]
    drv.run_until(run.close + run.mix.get("drain_s", 0.0), accept=False,
                  until=lambda: all(r.stamps for r in due))
    run.drain_end = time.perf_counter()
    run.compiles_in_window = counter.n
    run.reqs, run.lateness = drv.sent, drv.lateness
    devs = jax.devices()[:run.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    del drv, eng
    gc.collect()
    return peak
