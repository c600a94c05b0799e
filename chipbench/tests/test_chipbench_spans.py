"""The program's own ``repro.*`` spans: recorded by a tiny paged engine
under the profiler on the CPU and read back beside the harness's view,
the readers of those spans on hand-made traces whose answers are worked
out by hand, the idle-time split and the reconciliation of
``spans_report.py``, and the names of the jitted steps that the device
readers match."""
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import spans_report  # noqa: E402
from chipbench.harness import cell as cellmod  # noqa: E402
from chipbench.harness import spec  # noqa: E402
from chipbench.harness.cell import Run  # noqa: E402
from chipbench.harness.driver import annotation  # noqa: E402
from chipbench.harness.program_trace import ProgramTraceView  # noqa: E402
from chipbench.harness.trace import STEP_PROGRAMS, TraceView  # noqa: E402
from chipbench.harness.traffic import Req, prompt_tokens  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000                       # ns
SEED = 2**31 + 17
# (prompt, output): three chunks of 64, two, and one that ends at prefill
REQUESTS = ((160, 4), (96, 3), (40, 1))


@pytest.fixture(scope="module")
def model():
    return spec.load_json(DATA / "tiny.json")


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """A tiny paged engine serving REQUESTS to the end under the
    profiler, inside a ``chipbench.window`` annotation."""
    import jax
    from repro.inference.engine import Request
    eng = cellmod.build_engine(model, SEED)
    vocab = eng.cfg.vocab_size
    reqs = [Request(i, prompt=prompt_tokens(SEED, i, n, vocab),
                    max_new_tokens=m) for i, (n, m) in enumerate(REQUESTS)]
    tdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(tdir)):
        with annotation("window"):
            for r in reqs:
                eng.submit(r)
            while eng.tick():
                pass
    return eng, reqs, tdir


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_engine_spans_nest_and_carry_their_args(served, model):
    eng, reqs, tdir = served
    view = ProgramTraceView.from_dir(tdir)
    spans = {}
    for p in view.program:
        spans.setdefault(p[2], []).append(p)
    assert {"tick", "admit", "prepare", "prefill_chunk", "decode", "sync",
            "release", "launch.prefill_chunk", "launch.decode",
            "first_token"} <= set(spans)
    ticks = spans["tick"]
    for launch in spans["launch.decode"]:
        decode = [d for d in spans["decode"] if _inside(launch, d)]
        assert len(decode) == 1
        assert any(_inside(decode[0], t) for t in ticks)
        assert decode[0][3]["rows"] >= 1
    for launch in spans["launch.prefill_chunk"]:
        assert any(_inside(launch, c) for c in spans["prefill_chunk"])
    assert all(any(_inside(p, t) for t in ticks)
               for name in ("admit", "prepare", "prefill_chunk", "sync",
                            "release", "first_token") for p in spans[name])
    # one tick span per tick, counting what it ran
    assert [t[3]["step_num"] for t in ticks] == \
        list(range(ticks[0][3]["step_num"], ticks[0][3]["step_num"]
                   + len(ticks)))
    assert sum(t[3]["admitted"] for t in ticks) == len(reqs)
    assert sum(t[3]["prefill_chunks"] for t in ticks) == \
        len(spans["launch.prefill_chunk"]) == 3 + 2 + 1
    assert sum(t[3]["decode_rows"] for t in ticks) == \
        sum(d[3].get("rows", 0) for d in spans["decode"]) == \
        sum(m - 1 for _, m in REQUESTS)
    # the chunks of each prompt cover it, in order
    for r in reqs:
        chunks = [c[3] for c in spans["prefill_chunk"] if c[3]["rid"] == r.rid]
        assert [c["start"] for c in chunks] == \
            list(np.cumsum([0] + [c["tokens"] for c in chunks])[:-1])
        assert sum(c["tokens"] for c in chunks) == len(r.prompt)
    # a decode row attends to its KV entries and the new one: n + k + 1
    # at its k-th step
    assert sum(d[3].get("live_tokens", 0) for d in spans["decode"]) == \
        sum(n + k + 1 for n, m in REQUESTS for k in range(m - 1))
    assert {s[3]["what"] for s in spans["sync"]} == {"argmax", "logits"}
    bs = model["serving"]["block_size"]
    blocks = sum(s[3]["blocks"] for s in spans["release"])
    assert blocks == sum(-(-(n + m - 1) // bs) for n, m in REQUESTS)
    admits = {a[3]["rid"]: a[3] for a in spans["admit"]}
    assert set(admits) == {r.rid for r in reqs}
    assert all(a["queue_wait_ms"] >= 0 and "resume" not in a
               for a in admits.values())


def test_one_first_token_span_per_request(served):
    eng, reqs, tdir = served
    view = ProgramTraceView.from_dir(tdir)
    first = view.program_spans("first_token")
    assert sorted(f[3]["rid"] for f in first) == [r.rid for r in reqs]
    for f in first:
        a = f[3]
        assert 0 <= a["holdback_ms"] <= a["admit_to_token_ms"]
        assert a["queue_wait_ms"] >= 0
    # the engine's wall-clock histograms see the same requests
    reg = eng.registry
    assert reg.get("engine_queue_wait_seconds").count() == len(reqs)
    assert reg.get("engine_admit_to_token_seconds").count() == len(reqs)


def test_harness_view_keeps_only_its_own_annotations(served):
    _, _, tdir = served
    plain, view = TraceView.from_dir(tdir), ProgramTraceView.from_dir(tdir)
    assert [h[2] for h in plain.host] == ["window"]
    assert plain.host == view.host and plain.window == view.window
    assert view.program and all(not p[2].startswith("chipbench")
                                for p in view.program)


def test_step_programs_keep_the_names_the_readers_match(served):
    # the device readers find the step programs by these names; a renamed
    # body would silently null them
    import jax.numpy as jnp
    eng, _, _ = served
    be, B, nb = eng.backend, eng.B, eng.kv.nb_per_slot
    lengths = jnp.zeros(B, jnp.int32)
    decode = be._decode_paged.lower(
        be.params, eng.cache, jnp.zeros((B, 1), jnp.int32), lengths,
        jnp.zeros((B, nb), jnp.int32))
    chunk = be._prefill_paged.lower(
        be.params, eng.cache, jnp.zeros((1, 64), jnp.int32),
        jnp.zeros(nb, jnp.int32), jnp.asarray(0, jnp.int32))
    for kind, lowered in (("decode", decode), ("prefill", chunk)):
        name = re.match(r"module @(\S+)", lowered.as_text()).group(1)
        assert [k for k, pat in STEP_PROGRAMS.items()
                if pat.search(name)] == [kind], name


# ------------------------------------------------ readers, hand-made trace
def _view() -> ProgramTraceView:
    """Window 10-100 ms.  A decode program that started before any launch
    was recorded, a launch before the window whose program starts inside
    it, then a prefill chunk and two decode steps launched in the window,
    the last program on a device clock 0.5 ms ahead of its launch; four
    first tokens, one after the window."""
    programs = [[1 * MS, 5 * MS, "jit_paged_decode_body(1)"],
                [12 * MS, 18 * MS, "jit_paged_decode_body(1)"],
                [19 * MS, 20 * MS, "jit_scatter(7)"],
                [25 * MS, 40 * MS, "jit_paged_prefill_body(2)"],
                [45 * MS, 60 * MS, "jit_paged_decode_body(1)"],
                [70 * MS, 90 * MS, "jit_paged_decode_body(1)"]]
    ops = [p[:2] + ["fusion", "scope", "loop fusion"] for p in programs]
    program = [[8 * MS, 9 * MS, "launch.decode", {}],
               [20 * MS, 21 * MS, "launch.prefill_chunk", {}],
               [41 * MS, 43 * MS, "launch.decode", {}],
               [70.5 * MS, 71.5 * MS, "launch.decode", {}]]
    for i, (a2t, hold, t) in enumerate(((100.0, 10.0, 30), (200.0, 20.0, 60),
                                        (300.0, 60.0, 95),
                                        (900.0, 90.0, 101))):
        program.append([t * MS, t * MS, "first_token",
                        {"rid": i, "queue_wait_ms": 1.0,
                         "admit_to_token_ms": a2t, "holdback_ms": hold}])
    program.sort(key=lambda p: p[0])
    return ProgramTraceView(window=(10 * MS, 100 * MS),
                            devices={0: {"programs": programs, "ops": ops}},
                            host=[[10 * MS, 100 * MS, "window", {}]],
                            program=program)


def _run() -> Run:
    return Run(cell="c", model={}, mix={}, chips=1, peaks={})


def read(name, trace):
    return spec.metric_reader(name)(_run(), trace)


def test_launch_readers_pair_programs_with_their_launches():
    view = _view()
    pairs = view.launched_programs(0)
    assert [(s[0] / MS, p[0] / MS) for s, p in pairs] == \
        [(20, 25), (41, 45), (70.5, 70)]
    assert read("tklqt_ms", view) == pytest.approx((5 + 4 - 0.5) / 3)
    assert read("launch_ms.chat", view) == pytest.approx((1 + 2 + 1) / 3)


def test_first_token_readers():
    view = _view()
    assert read("admit_to_token_ms.chat", view) == \
        pytest.approx(200.0 + 0.9 * 100.0)
    assert read("token_holdback_ms.chat", view) == pytest.approx(30.0)


@pytest.mark.parametrize("name", spans_report.SPAN_METRICS)
def test_span_readers_are_silent_without_program_spans(name):
    plain = TraceView.load(DATA / "trace_chat.json.gz")
    empty = ProgramTraceView.from_json(plain.to_json())
    for trace in (None, plain, empty):
        assert read(name, trace) is None


def test_recorded_trace_loads_with_no_program_spans():
    plain = TraceView.load(DATA / "trace_chat.json.gz")
    view = ProgramTraceView.load(DATA / "trace_chat.json.gz")
    assert view.program == []
    assert view.host == plain.host and view.devices == plain.devices
    again = ProgramTraceView.from_json(_view().to_json())
    assert again.program == _view().program


# ------------------------------------------- spans_report on a made trace
def test_idle_time_split_by_the_span_open_in_it():
    view = _view()
    # idle between step programs, after the window opens: 18-19 ms, 20-25
    # (scatter covers 19-20), 40-45 and 60-70
    assert spans_report.idle_intervals(view, 0) == [
        (18 * MS, 19 * MS), (20 * MS, 25 * MS), (40 * MS, 45 * MS),
        (60 * MS, 70 * MS)]
    view.program += [[17 * MS, 26 * MS, "tick", {}],
                     [40 * MS, 60 * MS, "tick", {}],
                     [40 * MS, 42 * MS, "prepare", {}]]
    view.program.sort(key=lambda p: p[0])
    view.host += [[39 * MS, 61 * MS, "tick", {}],
                  [62 * MS, 66 * MS, "stamp", {}]]
    out = spans_report.attribute_idle(view)
    # 18-19 and 21-25 only the tick, 20-21 the chunk's launch; 40-41
    # prepare, 41-43 the decode launch, 43-45 only the ticks; 60-61 the
    # harness's tick alone, 62-66 the stamp, 61-62 and 66-70 nothing
    assert out["by_label_ms"] == pytest.approx({
        "repro.tick": 1 + 4 + 2, "repro.launch.prefill_chunk": 1,
        "repro.prepare": 1, "repro.launch.decode": 2,
        "chipbench.tick": 1, "chipbench.stamp": 4,
        "chipbench.outside": 1 + 4})
    assert out["total_ms"] == pytest.approx(21.0)
    assert out["attributed_share"] == pytest.approx(8 / 21)


def test_reconciliation_takes_out_wait_and_lateness():
    view = _view()
    reqs = [Req(0, 64, 4, due_abs=1.0, stamps=[1.1015]),
            Req(1, 64, 4, due_abs=2.0, stamps=[2.2031]),
            Req(2, 64, 4, due_abs=3.0, stamps=[3.3])]
    out = spans_report.reconcile(view, reqs, [0.0005, 0.002, 0.0])
    # 101.5 - 101 - 0.5, 203.1 - 201 - 2, 300 - 301 - 0
    assert out["n"] == 3
    assert out["median_ms"] == pytest.approx(0.0, abs=1e-6)
    assert out["min_ms"] == pytest.approx(-1.0)
    assert out["max_ms"] == pytest.approx(0.1)
