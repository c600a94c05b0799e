"""Observability layer: metrics registry semantics, registry-backed
EngineStats, SpanRecorder ring buffer, operator->kernel attribution
completeness (plan=eager AND plan=fused), live boundedness monitor vs
the offline sweep rule, strict Chrome-trace export with paired flow
events, and the shared strict-JSON sanitizer."""
import json
import math
from fractions import Fraction

import jax
import pytest

from repro.configs import get_config, reduced
from repro.core.device_model import KernelEvent
from repro.core.export import merged_chrome_trace
from repro.core.fusion import json_sanitize
from repro.inference.engine import Request, ServeEngine
from repro.models import init_params
from repro.telemetry.attribution import (AttributionReport, OperatorRow,
                                         attribute_events, merge_report,
                                         parse_operator)
from repro.telemetry.monitor import BoundednessMonitor
from repro.telemetry.registry import (Counter, MetricsRegistry,
                                      exponential_buckets)
from repro.telemetry.spans import SpanRecorder


# ------------------------------------------------------------ registry
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests served")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)

    g = reg.gauge("util", "pool utilization")
    g.set(0.25)
    g.add(0.5)
    assert g.value() == 0.75

    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 4
    assert h.sum() == pytest.approx(55.55)
    assert h.quantile(0.25) == 0.1
    assert h.quantile(1.0) == math.inf          # overflow bucket
    with pytest.raises(ValueError, match="q must be"):
        h.quantile(1.5)


def test_registry_labels_strict_and_get_or_create():
    reg = MetricsRegistry()
    c = reg.counter("bytes_total", labels=("direction",))
    c.inc(10, direction="evict")
    c.inc(4, direction="restore")
    assert c.value(direction="evict") == 10
    # full label set is mandatory — both missing and surplus labels fail
    with pytest.raises(ValueError, match="declared labels"):
        c.inc(1)
    with pytest.raises(ValueError, match="declared labels"):
        c.inc(1, direction="evict", extra="x")
    # get-or-create returns the SAME family; kind mismatch is a TypeError
    assert reg.counter("bytes_total", labels=("direction",)) is c
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("bytes_total")


def test_exponential_buckets_and_validation():
    b = exponential_buckets(1e-6, 2.0, 4)
    assert b == (1e-6, 2e-6, 4e-6, 8e-6)
    with pytest.raises(ValueError):
        exponential_buckets(0.0, 2.0, 4)
    with pytest.raises(ValueError):
        exponential_buckets(1.0, 1.0, 4)
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="strictly"):
        reg.histogram("bad", buckets=(1.0, 1.0, 2.0))


def test_snapshot_and_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("a_total", "help a").inc(2)
    reg.gauge("b", labels=("batch",)).set(1.5, batch=4)
    reg.histogram("c_seconds", buckets=(0.5, 1.0)).observe(0.7)
    snap = reg.snapshot()
    assert set(snap) == {"a_total", "b", "c_seconds"}
    assert snap["a_total"]["type"] == "counter"
    assert snap["a_total"]["series"][0]["value"] == 2.0
    assert snap["b"]["series"][0]["labels"] == {"batch": "4"}
    assert snap["c_seconds"]["series"][0]["value"]["count"] == 1
    assert snap["c_seconds"]["buckets"] == [0.5, 1.0]
    json.dumps(snap, allow_nan=False)           # plain strict JSON

    text = reg.to_prometheus()
    assert "# TYPE a_total counter" in text
    assert "a_total 2" in text
    assert 'b{batch="4"} 1.5' in text
    assert 'c_seconds_bucket{le="1"} 1' in text
    assert 'c_seconds_bucket{le="+Inf"} 1' in text
    assert "c_seconds_count 1" in text


# ------------------------------------------------------------ spans ring
def test_span_recorder_default_uncapped():
    rec = SpanRecorder()
    for i in range(100):
        rec.add(f"s{i}", "host", float(i), float(i) + 0.5)
    assert len(rec.spans) == 100 and rec.dropped == 0


def test_span_recorder_ring_buffer_keeps_newest():
    rec = SpanRecorder(max_spans=3)
    for i in range(5):
        rec.add(f"s{i}", "host", float(i), float(i) + 0.5)
    assert len(rec.spans) == 3
    assert [s.name for s in rec.spans] == ["s2", "s3", "s4"]
    assert rec.dropped == 2
    with pytest.raises(ValueError, match="max_spans"):
        SpanRecorder(max_spans=0)


def test_span_recorder_dropped_counter_binds_and_backfills():
    rec = SpanRecorder(max_spans=2)
    for i in range(4):
        rec.add(f"s{i}", "host", 0.0, 1.0)
    reg = MetricsRegistry()
    rec.bind_metrics(reg)       # backfills the 2 pre-bind evictions
    c = reg.get("telemetry_spans_dropped_total")
    assert isinstance(c, Counter) and c.value() == 2
    rec.add("s4", "host", 0.0, 1.0)
    assert rec.dropped == 3 and c.value() == 3
    rec.clear()                 # clears spans, keeps the monotonic counter
    assert rec.spans == [] and c.value() == 3


# ------------------------------------------------------------ attribution
def test_parse_operator_taxonomy():
    assert parse_operator("layer3/slot0/attn").op == "attention"
    assert parse_operator("layer3/slot0/attn").layer == 3
    assert parse_operator("layer0/slot1/mlp").op == "mlp"
    assert parse_operator("layer0/norm1").op == "norm"
    assert parse_operator("embed").op == "embed"
    assert parse_operator("draft/layer0/attn").op == "draft"
    assert parse_operator("layer1/slot0/attn", "psum").op == "collective"
    assert parse_operator("mystery_scope").op == "other"
    tag = parse_operator("layer2/slot0/attn")
    assert tag.key(by_layer=True) == "layer2/attention"
    assert tag.key() == "attention"


class _K:
    def __init__(self, name, operator):
        self.name = name
        self.operator = operator


class _Plan:
    def __init__(self, segments):
        self.segments = segments


def _ev(name, t_launch=1e-6, t_queue=2e-6, duration=3e-6):
    return KernelEvent(name=name, launch_begin=0.0, launch_end=t_launch,
                       kernel_start=t_launch + t_queue,
                       kernel_end=t_launch + t_queue + duration)


def test_attribute_events_fused_segment_splits_fractionally():
    kernels = [_K("dot", "layer0/slot0/attn"), _K("add", "layer0/norm1"),
               _K("mul", "layer0/slot0/mlp")]
    plan = _Plan([(0, 1), (2,)])       # fused 2-kernel segment + singleton
    events = [_ev("seg0"), _ev("seg1")]
    rep = attribute_events(kernels, plan, events)
    assert rep.total_events == 2
    assert rep.complete                       # exact Fraction arithmetic
    by_op = {r.operator: r for r in rep.rows}
    assert by_op["attention"].launches == Fraction(1, 2)
    assert by_op["norm"].launches == Fraction(1, 2)
    assert by_op["mlp"].launches == 1
    # fused segment's times split 50/50 across its two members' operators
    assert by_op["attention"].launch_s == pytest.approx(0.5e-6)
    assert by_op["mlp"].tklqt_s == pytest.approx(3e-6)
    # rows are ranked by TKLQT and export percentages that sum to 100
    dicts = rep.as_dicts()
    assert dicts == sorted(dicts, key=lambda d: -d["tklqt_us"])
    assert sum(d["tklqt_pct"] for d in dicts) == pytest.approx(100.0)


def test_attribute_events_draft_and_mismatch_guards():
    kernels = [_K("dot", "layer0/slot0/attn")]
    plan = _Plan([(0,)])
    rep = attribute_events(kernels, plan,
                           [_ev("draft_launch[0]"), _ev("seg0")])
    assert {r.operator for r in rep.rows} == {"draft", "attention"}
    assert rep.complete and rep.total_events == 2
    with pytest.raises(ValueError, match="more segment events"):
        attribute_events(kernels, plan, [_ev("a"), _ev("b")])
    with pytest.raises(ValueError, match="covered 0 of 1"):
        attribute_events(kernels, plan, [])


def test_merge_report_accumulates_calls():
    rep = AttributionReport(
        rows=[OperatorRow("attention", launches=Fraction(3), kernels=3,
                          launch_s=1e-6, queue_s=2e-6, exec_s=3e-6)],
        total_events=3)
    acc: dict = {}
    merge_report(acc, rep, calls=2)
    merge_report(acc, rep, calls=1)
    assert acc["attention"].launches == 9
    assert acc["attention"].launch_s == pytest.approx(3e-6)


# ------------------------------------------------------------ engine wiring
@pytest.fixture(scope="module")
def tiny_setup():
    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, params, plan, n=3, **kw):
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64, plan=plan, **kw)
    eng.run([Request(i, prompt=list(range(5, 13)), max_new_tokens=4)
             for i in range(n)])
    return eng


@pytest.mark.parametrize("plan", ["eager", "fused"])
def test_attribution_accounts_all_decode_dispatches(tiny_setup, plan):
    """ISSUE acceptance: 100% of decode dispatches attributed, exactly,
    under a one-segment-per-kernel plan AND a fused-rule plan."""
    cfg, params = tiny_setup
    eng = _serve(cfg, params, plan)
    rep = eng._planned_decode.attribution
    assert rep is not None
    assert rep.complete
    assert rep.accounted_launches == rep.total_events
    # the per-call timeline matches the engine's measured dispatch rate
    st = eng.stats
    assert rep.total_events == pytest.approx(st.dispatches_per_decode_step)
    ops = {r.operator for r in rep.rows}
    assert {"attention", "mlp", "norm"} <= ops


def test_engine_stats_is_registry_view(tiny_setup):
    cfg, params = tiny_setup
    eng = _serve(cfg, params, "eager")
    st, reg = eng.stats, eng.registry
    snap = reg.snapshot()
    # scalar counters read back through the registry, as ints
    assert isinstance(st.tokens_out, int) and st.tokens_out == 12
    assert snap["engine_tokens_out"]["series"][0]["value"] == 12
    assert snap["engine_decode_steps"]["series"][0]["value"] == \
        st.decode_steps
    # latency histograms populated from the same run
    h = reg.get("engine_step_time_seconds")
    assert h.count() == st.decode_steps
    assert reg.get("engine_ttft_seconds").count() == st.prefills
    # backend + monitor families registered alongside
    assert reg.get("backend_dispatches_total") is not None
    assert reg.get("monitor_inflection_batch") is not None
    text = reg.to_prometheus()
    assert "engine_tokens_out 12" in text


def test_engine_reset_gives_fresh_registry(tiny_setup):
    cfg, params = tiny_setup
    eng = _serve(cfg, params, "eager")
    old = eng.registry
    assert eng.stats.tokens_out > 0
    eng.reset()
    assert eng.registry is not old          # warmup metrics don't leak
    assert eng.stats.tokens_out == 0
    assert eng.registry.get("engine_step_time_seconds").count() == 0
    assert eng.monitor.result().batches == []
    # run again: the rebound instruments record into the new registry
    eng.run([Request(0, prompt=list(range(5, 13)), max_new_tokens=4)])
    assert eng.stats.tokens_out == 4
    assert eng.registry.get("engine_step_time_seconds").count() > 0


def test_kvcache_metrics_flow_through_engine_registry(tiny_setup):
    cfg, params = tiny_setup
    eng = _serve(cfg, params, "eager", cache="paged", block_size=8)
    snap = eng.registry.snapshot()
    alloc = snap["kvcache_blocks_allocated_total"]["series"][0]["value"]
    freed = snap["kvcache_blocks_freed_total"]["series"][0]["value"]
    assert alloc > 0 and freed > 0
    # every page handed out came back once every request finished
    assert alloc == freed
    assert snap["kvcache_blocks_used"]["series"][0]["value"] == 0


def test_monitor_matches_offline_sweep_rule(tiny_setup):
    """ISSUE acceptance: the live monitor's transition batch equals
    classify_measured_sweep over the same (batch, step, tax) data."""
    cfg, params = tiny_setup
    from repro.telemetry.characterize import classify_measured_sweep
    mon = BoundednessMonitor()
    batches, steps, taxes = [], [], []
    for b in (1, 2, 4):
        # uniform closed workload: every request identical, max_batch=b,
        # so every decode step runs at the full batch and the monitor's
        # bucket means equal the run means
        eng = ServeEngine(cfg, params, max_batch=b, max_len=64,
                          plan="eager", monitor=mon)
        eng.run([Request(i, prompt=list(range(5, 13)), max_new_tokens=4)
                 for i in range(b)])
        st = eng.stats
        batches.append(b)
        steps.append(sum(st.step_times_s) / len(st.step_times_s))
        taxes.append(st.launch_tax_per_decode_step_s)
    live = mon.result()
    offline = classify_measured_sweep(batches, steps, taxes)
    assert live.batches == batches
    assert live.inflection_batch == offline.inflection_batch
    for b in batches:
        assert live.classify(b) == offline.classify(b)
    assert mon.verdict() in ("CPU-bound", "GPU-bound")
    # operator attribution rode along from every planned decode call
    top = mon.top_operators(k=3)
    assert top and top[0][2] >= top[-1][2]
    assert {op for op, _, _ in mon.top_operators(k=10)} >= \
        {"attention", "mlp", "norm"}
    summary = mon.summary()
    json.dumps(json_sanitize(summary), allow_nan=False)
    assert summary["classification"] and summary["top_operators"]


def test_monitor_off_and_empty_verdict(tiny_setup):
    cfg, params = tiny_setup
    eng = ServeEngine(cfg, params, max_batch=1, max_len=64, monitor=False)
    assert eng.monitor is None
    eng.run([Request(0, prompt=[3, 4, 5, 6], max_new_tokens=2)])
    assert eng.stats.tokens_out == 2        # telemetry-off still serves
    assert BoundednessMonitor().verdict() == "unknown"
    with pytest.raises(ValueError, match="window"):
        BoundednessMonitor(window=0)


# ------------------------------------------------------------ chrome trace
def _check_flow_pairing(trace):
    """Every dispatch_flow id must pair exactly one host start (``s``)
    with exactly one device finish (``f``)."""
    starts, finishes = {}, {}
    for ev in trace["traceEvents"]:
        assert ev["ph"] in ("X", "s", "f")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["ts"], (int, float))
        if ev.get("cat") == "dispatch_flow":
            side = starts if ev["ph"] == "s" else finishes
            assert ev["id"] not in side, f"duplicate flow id {ev['id']}"
            side[ev["id"]] = ev
    assert set(starts) == set(finishes)
    return starts, finishes


def test_merged_trace_flow_pairs_per_anchor(tiny_setup):
    cfg, params = tiny_setup
    rec = SpanRecorder()
    eng = _serve(cfg, params, "eager", telemetry=rec)
    events = eng._planned_decode.modeled_events
    anchors = [s.t0 for s in rec.by_cat("decode")][:2]
    assert len(anchors) == 2
    trace = merged_chrome_trace(rec.spans, "TPU-v5e",
                                device_events=events,
                                device_anchors=anchors)
    json.dumps(trace, allow_nan=False)
    starts, finishes = _check_flow_pairing(trace)
    assert len(starts) == len(events) * len(anchors)
    for fid, s in starts.items():
        assert s["tid"] == 1 and finishes[fid]["tid"] == 2
    names = trace["otherData"]["thread_names"]
    assert set(names) == {"0", "1", "2"}


# ------------------------------------------------------------ strict JSON
def test_json_sanitize_nested_inf_nan():
    payload = {"a": float("inf"), "b": [float("nan"), 1.5],
               "c": {"d": (float("-inf"), "ok")}, "e": 3}
    out = json_sanitize(payload)
    json.dumps(out, allow_nan=False)                 # would raise unsanitized
    assert out["a"] == "inf" and out["b"][0] == "nan"
    assert out["c"]["d"] == ["-inf", "ok"] and out["e"] == 3
    with pytest.raises(ValueError):
        json.dumps(payload, allow_nan=False)


def test_bench_run_sanitizer_is_shared_helper():
    from benchmarks.run import _json_sanitize
    assert _json_sanitize({"x": float("inf")}) == {"x": "inf"}
