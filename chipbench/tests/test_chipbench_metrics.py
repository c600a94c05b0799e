"""Each metric reader on a hand-made trace and run record whose answers
are worked out by hand, and on a small trace recorded on a TPU v5e."""
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import spec  # noqa: E402
from chipbench.harness.cell import Run  # noqa: E402
from chipbench.harness.trace import TraceView  # noqa: E402
from chipbench.harness.traffic import Req  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
BENCH = spec.load_benchmark(ROOT)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000                       # ns


def _device() -> dict:
    scope = "jit(paged_decode_body)/while/body/slot0/{}/dot_general"
    pscope = "jit(paged_prefill_body)/while/body/slot0/{}/dot_general"
    loop = "jit(paged_decode_body)/while"
    ops = [[10 * MS, 30 * MS, "while.1", loop, "while"],
           [10 * MS, 20 * MS, "fusion.1", scope.format("attn"), "loop fusion"],
           [20 * MS, 30 * MS, "fusion.2", scope.format("mlp"), "loop fusion"],
           [32 * MS, 34 * MS, "scatter.1", "jit(scatter)/scatter", "scatter"],
           [40 * MS, 60 * MS, "fusion.3", pscope.format("attn"),
            "loop fusion"],
           [70 * MS, 90 * MS, "while.1", loop, "while"],
           [70 * MS, 80 * MS, "fusion.1", scope.format("attn"), "loop fusion"],
           [80 * MS, 90 * MS, "fusion.2", scope.format("mlp"), "loop fusion"]]
    return {"programs": [[10 * MS, 30 * MS, "jit_paged_decode_body"],
                         [32 * MS, 34 * MS, "jit_scatter"],
                         [40 * MS, 60 * MS, "jit_paged_prefill_body"],
                         [70 * MS, 90 * MS, "jit_paged_decode_body"]],
            "ops": ops}


def _trace(chips: int = 1) -> TraceView:
    host = [[0, 100 * MS, "window", {}],
            [9 * MS, 10 * MS, "decode",
             {"attn_flops": 1e9, "attn_bytes": 4.095e9, "model_flops": 5e11}],
            [39 * MS, 40 * MS, "prefill_chunk",
             {"tokens": 512, "attn_flops": 1e9, "attn_bytes": 0.0,
              "model_flops": 4.85e11}],
            [61 * MS, 69 * MS, "sleep", {}],
            [69 * MS, 70 * MS, "decode",
             {"attn_flops": 1e9, "attn_bytes": 4.095e9, "model_flops": 0.0}]]
    return TraceView(window=(0, 100 * MS),
                     devices={d: _device() for d in range(chips)},
                     host=host)


def _run(chips: int = 1) -> Run:
    r = Run(cell="c", model={}, mix={}, chips=chips, peaks=PEAKS,
            setup_s=12.5, open=10.0, close=20.0, drain_end=31.0)
    r.reqs = [Req(0, 64, 4, due_abs=11.0, stamps=[11.5, 12.0, 12.5, 13.0]),
              Req(1, 128, 4, due_abs=12.0, stamps=[12.2, 12.6, 19.9, 20.1]),
              Req(2, 64, 4, due_abs=19.0),          # never served
              Req(3, 256, 4, due_abs=8.0, stamps=[9.0, 10.5])]
    return r


def read(name, run, trace):
    return spec.metric_reader(name)(run, trace)


def test_device_readers_on_a_hand_made_trace():
    run, tv = _run(), _trace()
    assert read("idle_share.chat", run, tv) == pytest.approx(38.0)
    assert read("decode_step_ms.chat", run, tv) == pytest.approx(20.0)
    assert read("prefill_ms_per_ktok.batch", run, tv) == \
        pytest.approx(20.0 / 0.512)
    # gaps 30-40 ms (2 ms of it busy) and 60-70 ms (the harness slept),
    # over three step programs
    assert read("host_gap_ms.chat", run, tv) == pytest.approx(8.0 / 3)
    # 8.19e9 bytes at 819e9 B/s need 10 ms; attn ops took 40 ms
    assert read("attention_roofline.chat", run, tv) == pytest.approx(25.0)
    assert read("step_mfu.batch", run, tv) == \
        pytest.approx(100 * 9.85e11 / (0.1 * 197e12))


def test_device_readers_average_over_the_cells_chips():
    run, tv = _run(chips=2), _trace(chips=2)
    assert read("decode_step_ms.batch", run, tv) == pytest.approx(20.0)
    assert read("idle_share.batch", run, tv) == pytest.approx(38.0)


def test_trace_readers_are_silent_without_a_trace():
    run = _run()
    for m in BENCH["per_layer"]:
        assert read(m["name"], run, None) is None


def test_host_clock_readers():
    run = _run()
    # due in window: requests 0, 1, 2; request 2 waits until the drain ends
    assert read("ttft_p95_ms", run, None) == pytest.approx(
        1e3 * (0.5 + 0.9 * (12.0 - 0.5)))
    gaps = sorted([0.5, 0.5, 0.5, 0.4, 7.3])
    want = gaps[3] + 0.8 * (gaps[4] - gaps[3])
    assert read("itl_p95_ms", run, None) == pytest.approx(1e3 * want)
    assert read("setup_s", run, None) == 12.5


def test_trace_view_round_trips():
    tv = _trace(chips=2)
    again = TraceView.from_json(tv.to_json())
    assert again.devices.keys() == tv.devices.keys()
    assert again.window == tv.window and again.host == tv.host


def test_readers_on_a_recorded_trace():
    # 1.2 s of smollm-chat on one TPU v5e: five decode steps, prefill
    # chunks, page zeroing, the harness's annotations
    tv = TraceView.load(DATA / "trace_chat.json.gz")
    run = _run()
    vals = {m["name"]: read(m["name"], run, tv) for m in BENCH["per_layer"]
            if "smollm-chat" in m["workloads"]}
    assert all(v is not None and math.isfinite(v) for v in vals.values())
    for name, v in vals.items():
        if BENCH_UNITS[name] == "%":
            assert 0 < v <= 100, (name, v)
        else:
            assert v > 0, (name, v)


BENCH_UNITS = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
