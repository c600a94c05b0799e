"""Fused decode path: Pallas fused kernels vs refs (interpret mode), the
fusion-rule registry matching/substituting on real traces, plan-table
round-trips, and the serving engine's fused-plan dispatch accounting."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.fusion import FusionOutcome, json_safe
from repro.core.tracing import trace_fn
from repro.inference.engine import Request, ServeEngine
from repro.kernels.fused import residual_rmsnorm, rmsnorm_matmul
from repro.kernels.fused.residual_rmsnorm.ref import residual_rmsnorm_ref
from repro.kernels.fused.rmsnorm_matmul.ref import rmsnorm_matmul_ref
from repro.layers.common import rmsnorm as rmsnorm_layer
from repro.models import forward, init_params, make_cache
from repro.runtime import (LaunchPlan, PlanExecutor, Planner, find_matches,
                           fused_plan)
from repro.runtime.autotune import (AutotuneEntry, CandidateResult, PlanTable,
                                    autotune, select)


# ------------------------------------------------------------ kernel numerics
@pytest.mark.parametrize("shape", [(1, 1, 64), (2, 3, 32), (5, 128)])
def test_residual_rmsnorm_matches_ref(shape):
    d = shape[-1]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], shape)
    r = jax.random.normal(ks[1], shape)
    w = jax.random.normal(ks[2], (d,))
    y, s = residual_rmsnorm(x, w, r, interpret=True)
    y_ref, s_ref = residual_rmsnorm_ref(x.reshape(-1, d), w,
                                        r.reshape(-1, d))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, d),
                               np.asarray(y_ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s).reshape(-1, d),
                               np.asarray(s_ref), atol=1e-6)


def test_plain_rmsnorm_matches_layer_oracle():
    """Without a residual the fused kernel must equal layers.common.rmsnorm
    — the exact op the decode trace windows come from."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 1, 48))
    w = jax.random.normal(jax.random.PRNGKey(1), (48,))
    y, s = residual_rmsnorm(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(rmsnorm_layer(x, w)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(x), atol=0)


@pytest.mark.parametrize("n,d,f", [(1, 64, 128), (7, 32, 48), (16, 64, 64)])
def test_rmsnorm_matmul_matches_ref(n, d, f):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (n, d))
    w = jax.random.normal(ks[1], (d,))
    p = jax.random.normal(ks[2], (d, f))
    y, normed = rmsnorm_matmul(x, w, p, interpret=True)
    y_ref, normed_ref = rmsnorm_matmul_ref(x, w, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(normed), np.asarray(normed_ref),
                               atol=1e-6)


# ------------------------------------------------------------ rule registry
def _decode_setup(n_layers=2, **overrides):
    cfg = reduced(get_config("smollm-360m"), n_layers=n_layers, **overrides)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = make_cache(cfg, 1, 64, src_len=1, dtype=cfg.cdtype)
    toks = jnp.zeros((1, 1), jnp.int32)
    lengths = jnp.ones((1,), jnp.int32)

    def decode_body(params, cache, tokens, lengths):
        logits, _, cache2 = forward(params, tokens, cfg, cache=cache,
                                    lengths=lengths, unroll=True)
        return logits[:, 0], cache2

    trace = trace_fn(decode_body, params, cache, toks, lengths)
    return cfg, params, trace, (params, cache, toks, lengths)


def test_rules_match_real_decode_trace():
    _, _, trace, _ = _decode_setup()
    matches = find_matches(trace)
    names = [m.rule_name for m in matches]
    # the decode trace has both block-boundary norms and norm->projection
    assert "residual_rmsnorm" in names
    assert "rmsnorm_matmul" in names
    for m in matches:
        # verified numeric equivalence on every substituted window
        assert m.max_abs_err <= 1e-4
        # windows are disjoint, in order
        assert m.stop - m.start == len(m.indices)
    starts = [m.start for m in matches]
    assert starts == sorted(starts)
    for a, b in zip(matches, matches[1:]):
        assert a.stop <= b.start


def test_rules_match_bf16_decode_trace():
    """The published dtype: a bf16 stream converts to f32 around the norm,
    and every rule still binds and passes its check, within the bf16
    rounding allowance."""
    _, _, trace, args = _decode_setup(param_dtype="bfloat16",
                                      compute_dtype="bfloat16")
    matches = find_matches(trace)
    assert {m.rule_name for m in matches} == {
        "rmsnorm_matmul", "residual_rmsnorm", "rmsnorm"}
    for m in matches:
        assert 0.0 < m.round_bound
        assert m.max_abs_err <= 1e-4 + m.round_bound
    n = len(trace.kernels)
    eager, _ = PlanExecutor(trace, LaunchPlan.eager(n)).run(*args)
    out, _ = PlanExecutor(trace, fused_plan(trace, matches=matches)).run(*args)
    for a, b in zip(eager, out):
        a = np.asarray(a, np.float32)
        tol = 2 * float(jnp.finfo(jnp.bfloat16).eps) * max(
            float(np.max(np.abs(a))), 1.0)
        np.testing.assert_allclose(a, np.asarray(b, np.float32), atol=tol)


def test_fused_plan_is_exact_cover_with_rule_tags():
    _, _, trace, _ = _decode_setup()
    plan = fused_plan(trace)            # eager base
    plan.validate(len(trace.kernels))
    assert plan.strategy == "fused"
    assert plan.n_fused_rules > 0
    assert plan.n_launches < len(trace.kernels)
    rule_segs = dict(plan.rules)
    for si, name in rule_segs.items():
        assert len(plan.segments[si]) > 1
        assert name in plan.rule_names()
    # cache identity distinguishes rule-tagged plans
    assert plan.key() != LaunchPlan.eager(len(trace.kernels)).key()


def test_fused_plan_outputs_equal_eager():
    _, _, trace, args = _decode_setup()
    n = len(trace.kernels)
    eager, _ = PlanExecutor(trace, LaunchPlan.eager(n)).run(*args)
    for base in (None, Planner(trace, "GH200").auto().plan):
        plan = fused_plan(trace, base=base)
        out, _ = PlanExecutor(trace, plan).run(*args)
        for a, b in zip(eager, out):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-5)


def test_planner_fused_rules_beats_eager_launches():
    _, _, trace, _ = _decode_setup()
    planner = Planner(trace, "GH200")
    plan = planner.fused_rules()
    assert plan.n_fused_rules > 0
    assert plan.n_launches < planner.eager().n_launches
    # modeled report prices the plan without error
    assert planner.evaluate(plan).tklqt > 0.0


# ------------------------------------------------------------ serving engine
def test_engine_fused_plan_fewer_dispatches_same_tokens():
    """Acceptance: at batch=1 the fused-rules plan decodes with fewer
    dispatches per step than eager, hits fusion rules every step, and
    generates identical tokens."""
    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def run(plan):
        eng = ServeEngine(cfg, params, max_batch=1, max_len=64, plan=plan)
        done = eng.run([Request(0, prompt=list(range(7, 17)),
                                max_new_tokens=4)])
        return [r.generated for r in done], eng.stats

    toks_eager, s_eager = run("eager")
    toks_fused, s_fused = run("fused")
    assert toks_eager == toks_fused
    assert s_fused.dispatches_per_decode_step \
        < s_eager.dispatches_per_decode_step
    assert s_fused.fused_dispatches_per_decode_step > 0
    assert s_fused.rule_hits and all(v > 0
                                     for v in s_fused.rule_hits.values())
    assert s_eager.fused_dispatches == 0 and not s_eager.rule_hits


# ------------------------------------------------------------ autotuner
def _table():
    def cand(plan, step_us, disp):
        return CandidateResult(
            plan=plan, mean_decode_step_s=step_us * 1e-6,
            decode_launch_tax_s=0.0, dispatches_per_decode_step=disp,
            fused_dispatches_per_decode_step=0.0, tokens_per_s=1.0,
            decode_steps=10)

    t = PlanTable(arch="smollm-360m", scenario="chatbot",
                  platform="TPU-v5e")
    t.entries[1] = AutotuneEntry(
        batch=1, region="CPU-bound", selected="fused",
        candidates=[cand("eager", 100.0, 331), cand("fused", 40.0, 13)])
    t.entries[8] = AutotuneEntry(
        batch=8, region="GPU-bound", selected="jit",
        candidates=[cand("jit", 20.0, 1)])
    return t


def test_plan_table_round_trip(tmp_path):
    t = _table()
    path = t.save(str(tmp_path / "plan_table.json"))
    loaded = PlanTable.load(path)
    assert loaded.to_dict() == t.to_dict()
    assert loaded.lookup(1) == "fused"
    assert loaded.lookup(8) == "jit"
    # between entries -> nearest at/below; below all -> smallest
    assert loaded.lookup(4) == "fused"
    assert loaded.lookup(64) == "jit"
    assert PlanTable.from_any(path).lookup(1) == "fused"
    assert PlanTable.from_any(loaded.to_dict()).lookup(8) == "jit"
    with pytest.raises(ValueError):
        PlanTable.from_dict({"version": 99})
    assert PlanTable("a", "s", "p").lookup(4) == "auto"


def test_select_prefers_fewer_dispatches_on_tie():
    def cand(plan, step_us, disp):
        return CandidateResult(
            plan=plan, mean_decode_step_s=step_us * 1e-6,
            decode_launch_tax_s=0.0, dispatches_per_decode_step=disp,
            fused_dispatches_per_decode_step=0.0, tokens_per_s=1.0,
            decode_steps=10)

    assert select([cand("eager", 100, 331), cand("fused", 50, 13)]) == "fused"
    # within the tie band the lower dispatch count wins
    assert select([cand("chain", 50.2, 191), cand("fused", 50.0, 13),
                   cand("eager", 100, 331)], tie_rel_tol=0.05) == "fused"
    assert select([cand("fused", 50.0, 13), cand("chain", 49.9, 191)],
                  tie_rel_tol=0.05) == "fused"


def test_autotune_emits_fused_or_chain_in_cpu_bound_region(tmp_path):
    """Mini end-to-end: autotune one CPU-bound batch point, persist the
    table, and serve with plan='autotuned' resolving from it."""
    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    result = autotune(cfg, params, scenario="chatbot", batches=(1,),
                      n_requests=3, prompt_cap=12, output_cap=4,
                      max_len=64)
    entry = result.table.entries[1]
    assert entry.region == "CPU-bound"     # single point: flat curve
    assert entry.selected in ("fused", "chain")
    assert {c.plan for c in entry.candidates} == {"eager", "chain", "fused"}
    path = result.table.save(str(tmp_path / "plan_table.json"))

    eng = ServeEngine(cfg, params, max_batch=1, max_len=64,
                      plan="autotuned", plan_table=path)
    assert eng.plan == entry.selected
    assert eng.plan_label == f"autotuned:{entry.selected}"
    done = eng.run([Request(0, prompt=[3, 5, 7], max_new_tokens=2)])
    assert len(done) == 1 and len(done[0].generated) == 2
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, max_batch=1, plan="autotuned")


# ------------------------------------------------------------ json export
def test_fusion_outcome_json_safe():
    """Regression: inf/nan speedups must serialize as STRICT json — a
    0-cost fused run used to emit bare Infinity/NaN tokens."""
    out = FusionOutcome(length=8, k_eager=10, k_fused=2,
                        ideal_speedup=5.0, eager_host_s=1.0,
                        fused_host_s=0.0,
                        measured_speedup=float("inf"),
                        max_abs_err=float("nan"))
    payload = json.dumps(out.row(), allow_nan=False)   # must not raise
    parsed = json.loads(payload)
    assert parsed["measured_speedup"] == "inf"
    assert parsed["max_abs_err"] == "nan"
    assert parsed["ideal_speedup"] == 5.0
    assert json_safe(2.5) == 2.5 and json_safe(float("-inf")) == "-inf"
    assert math.isnan(float("nan"))  # sanity: nan stays nan pre-export

    from benchmarks.run import _json_sanitize
    nested = {"rows": [{"us_per_call": float("inf"), "ok": 1.0}]}
    safe = json.dumps(_json_sanitize(nested), allow_nan=False)
    assert json.loads(safe)["rows"][0]["us_per_call"] == "inf"
