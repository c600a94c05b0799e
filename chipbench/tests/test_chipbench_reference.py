"""The plain reference and its control, at a size a CPU test run holds.

The reference is checked against the program's own ``forward`` in
float32 (it must agree to rounding), the benchmark's weight draws are
checked layer by layer against the stacked tree the program serves, and
the float8 control must fail the tiny configuration's limit where the
bfloat16 program passes it."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench.harness import cell, check, weights  # noqa: E402
from chipbench.reference import dense_decoder as ref  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
TINY = json.loads((DATA / "tiny.json").read_text())


def _logits(model, seed, toks):
    from repro.models import forward
    cfg = cell.repro_config(model)
    params = weights.program_params(cfg, seed)
    lg, _, _ = forward(params, jnp.asarray(toks), cfg)
    return np.asarray(lg, np.float32)


@pytest.mark.parametrize("variant", [
    {},
    {"tie_word_embeddings": False, "qkv_bias": True, "rope_theta": 1e6,
     "registry": "codeqwen1.5-7b"}])
def test_reference_matches_forward_in_float32(variant):
    model = dict(TINY, torch_dtype="float32", **variant)
    toks = np.random.default_rng(0).integers(0, model["vocab_size"], (2, 96))
    got = _logits(model, 3, toks)
    want = ref.logits_at(model, weights.seed_key(3), [list(t) for t in toks],
                         [list(range(96))] * 2)
    for i in range(2):
        np.testing.assert_allclose(got[i], want[i], atol=2e-5, rtol=0)


def test_layer_draws_equal_the_served_stack():
    cfg = cell.repro_config(TINY)
    params = weights.program_params(cfg, 2**40 + 1)
    key = weights.seed_key(2**40 + 1)
    dims = tuple(sorted(ref.dims(TINY).items()))
    for layer in range(TINY["num_hidden_layers"]):
        drawn = ref._layer_weights(dims, key, layer)
        for path, value in drawn.items():
            leaf = params["blocks"]["slot0"]
            for p in path.split("/"):
                leaf = leaf[p]
            assert np.array_equal(np.asarray(leaf[layer], np.float32),
                                  np.asarray(value)), (layer, path)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_where_bfloat16_passes(seed):
    limit = TINY["check"]["logit_gap_max"]
    toks = np.random.default_rng(seed).integers(0, TINY["vocab_size"],
                                                (2, 256))
    seqs, pos = [list(t) for t in toks], [list(range(256))] * 2
    key = weights.seed_key(seed)
    want = ref.logits_at(TINY, key, seqs, pos, "f32")
    served = _logits(TINY, seed, toks)
    control = ref.logits_at(TINY, key, seqs, pos, "fp8")
    assert check.gaps(want, [s.argmax(-1) for s in served]).max() <= limit
    assert check.gaps(want, [c.argmax(-1) for c in control]).max() > limit


def test_bfloat16_witness_reads_like_the_program():
    seed = 4
    toks = np.random.default_rng(seed).integers(0, TINY["vocab_size"],
                                                (2, 256))
    seqs, pos = [list(t) for t in toks], [list(range(256))] * 2
    key = weights.seed_key(seed)
    want = ref.logits_at(TINY, key, seqs, pos, "f32")
    low = ref.logits_at(TINY, key, seqs, pos, "bf16")
    g = check.gaps(want, [lg.argmax(-1) for lg in low])
    assert 0 < g.max() <= TINY["check"]["logit_gap_max"]
    assert not np.array_equal(np.concatenate(want), np.concatenate(low))


def test_seed_key_takes_seeds_past_32_bits():
    a = jax.random.key_data(weights.seed_key(2**31 + 5))
    b = jax.random.key_data(weights.seed_key(2**33 + 5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
