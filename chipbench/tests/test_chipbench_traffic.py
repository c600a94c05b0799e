"""The traffic generator: deterministic per seed, the same work for every
seed, prompt lengths rounded to the chunk grid and within the context."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import cell, spec, traffic  # noqa: E402

BENCH = spec.load_benchmark(ROOT)
CELLS = [c["name"] for c in BENCH["workloads"]]


def _mix(name):
    c = spec.find_cell(BENCH, name)
    return spec.load_traffic(c["traffic"]), spec.load_config(c["config"])


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_requests(name):
    mix, model = _mix(name)
    seed = 2**31 + 12345
    a = traffic.generate(mix, seed, BENCH["run_seconds"])
    b = traffic.generate(mix, seed, BENCH["run_seconds"])
    assert [(r.prompt_len, r.max_new, r.due_s) for r in a] == \
        [(r.prompt_len, r.max_new, r.due_s) for r in b]
    assert traffic.prompt_tokens(seed, 3, 64, model["vocab_size"]) == \
        traffic.prompt_tokens(seed, 3, 64, model["vocab_size"])


@pytest.mark.parametrize("name", CELLS)
def test_seeds_change_order_not_work(name):
    mix, _ = _mix(name)
    a = traffic.generate(mix, 1, BENCH["run_seconds"])
    b = traffic.generate(mix, 2, BENCH["run_seconds"])
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]


@pytest.mark.parametrize("name", CELLS)
def test_lengths_on_grid_and_within_context(name):
    mix, model = _mix(name)
    reqs = traffic.generate(mix, 7, BENCH["run_seconds"])
    step = mix["round_prompt_to"]
    assert all(r.prompt_len % step == 0 for r in reqs)
    assert all(mix["prompt"]["min"] <= r.prompt_len <= mix["prompt"]["max"]
               for r in reqs)
    assert all(r.prompt_len + r.max_new <= model["serving"]["max_len"]
               for r in reqs)


def test_paced_mix_puts_the_same_work_in_every_window():
    mix, _ = _mix("smollm-chat")
    seconds, lead = BENCH["run_seconds"], mix["lead_in_s"]

    def window(seed):
        return [r for r in traffic.generate(mix, seed, seconds)
                if lead <= r.due_s < lead + seconds]
    a, b = window(3), window(2**32 + 3)
    assert len(a) == len(b) == round(mix["rate_rps"] * seconds)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]


def test_lognormal_quantiles_match_the_median():
    d = {"kind": "lognormal", "median": 512, "sigma": 0.6,
         "min": 64, "max": 1536}
    ls = traffic.lengths(d, 1001)
    assert sorted(ls)[500] == 512
    assert min(ls) >= 64 and max(ls) <= 1536


def test_warm_up_covers_every_chunk_length():
    mix = spec.load_traffic("chat")
    chunk = spec.load_config("smollm-360m")["serving"]["prefill_chunk"]
    assert cell.chunk_lengths(mix, chunk) == list(range(64, 513, 64))


def test_outputs_are_cut_only_where_the_context_forces():
    mix, model = _mix("smollm-chat")
    uncut = dict(mix)
    del uncut["max_total"]
    a = traffic.generate(mix, 11, BENCH["run_seconds"])
    b = traffic.generate(uncut, 11, BENCH["run_seconds"])
    assert [r.prompt_len for r in a] == [r.prompt_len for r in b]
    assert [r.max_new for r in a] == [
        min(r.max_new, mix["max_total"] - r.prompt_len) for r in b]
    assert any(r.max_new > s.max_new for r, s in zip(b, a))
    assert mix["max_total"] <= model["serving"]["max_len"]


def test_only_poisson_arrivals():
    mix, _ = _mix("smollm-chat")
    with pytest.raises(ValueError, match="unknown arrival"):
        traffic.generate(dict(mix, arrival="closed"), 0, 10)
