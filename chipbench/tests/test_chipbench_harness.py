"""The harness end to end at a tiny size on the CPU, past its look for a
chip: it finds a traffic mix and a metric it has never seen by name, it
judges a sound run correct, and it judges a run incorrect when the timed
path is broken underneath, or when the float8 control takes the
program's place.  And the command itself refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import control  # noqa: E402
from chipbench import run as runmod  # noqa: E402
from chipbench.harness import cell as cellmod  # noqa: E402
from chipbench.harness import faults, spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
NEW_METRIC = '''"""Requests completed in the window."""


def read(run, trace):
    return float(sum(1 for r in run.reqs if r.status == "done"
                     and r.stamps and run.in_window(r.stamps[-1])))
'''


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    """A copy of the benchmark's directories with a configuration, a
    traffic mix and a metric that the harness has never seen."""
    b = tmp_path_factory.mktemp("chipbench")
    shutil.copytree(spec.BENCH_DIR, b, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(DATA / "tiny.json", b / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny-chat.json", b / "traffic" / "tiny-chat.json")
    (b / "metrics" / "done_in_window.py").write_text(NEW_METRIC)
    peaks = json.loads((b / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
    (b / "peaks.json").write_text(json.dumps(peaks))
    return b


def _bench():
    bench = spec.load_benchmark(ROOT)
    cell = {"name": "tiny-chat", "config": "tiny", "traffic": "tiny-chat",
            "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    bench["end_to_end"].append({"name": "done_in_window", "unit": "1",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny-chat"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "itl_p95_ms"):
            m["workloads"].append("tiny-chat")
    return bench, cell


def _run(base, tmp_path, seed=2**31 + 9):
    import jax
    bench, cell = _bench()
    return runmod.run_cell(bench, cell, seed, 2.0, False, base,
                           jax.devices(), trace_dir=tmp_path)


def test_sound_run_is_correct_and_reads_new_files(base, tmp_path):
    out = _run(base, tmp_path)
    assert out["correct"] is True
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s",
                                   "done_in_window"}
    assert out["metrics"]["done_in_window"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["tokens_checked"]["value"] >= \
        out["checks"]["tokens_checked"]["limit"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_timed_path_is_incorrect(base, tmp_path, monkeypatch, fault):
    monkeypatch.setattr(cellmod, "build_engine",
                        faults.planted(cellmod.build_engine, fault))
    out = _run(base, tmp_path)
    assert out["correct"] is False
    assert out["checks"]["logit_gap_max"]["value"] > \
        out["checks"]["logit_gap_max"]["limit"]


def test_control_in_the_programs_place_is_incorrect(base):
    # at this size float8 flips a served token on about half of the seeds
    # (the program on none); this seed is one where it does
    model = spec.load_config("tiny", base)
    run = cellmod.Run(cell="tiny-chat", model=model,
                      mix=spec.load_traffic("tiny-chat", base), chips=1,
                      peaks={})
    seed = 2**31 + 11
    cellmod.execute(run, seed, 2.0, time.perf_counter())
    row = control.readings_row(model, run, seed, control=True)
    assert row["program"]["correct"] is True
    assert row["control"]["correct"] is False
    ctl = row["control"]["checks"]
    assert ctl["logit_gap_max"]["value"] > ctl["logit_gap_max"]["limit"]
    assert ctl["tokens_checked"]["value"] == \
        row["program"]["checks"]["tokens_checked"]["value"]


def test_command_without_a_tpu_fails_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chipbench" / "run.py"),
                        "--workload", "smollm-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr
