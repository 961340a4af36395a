"""The float32 reference against the program's own model on tiny
configurations of both benchmark models, so a fault in either shows: full
logits of a teacher-forced sequence, and tokens served through the paged
int8 pool, prefill and decode."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import artifact, reference, weights

HERE = Path(__file__).parent


def tiny(name):
    return json.loads((HERE / "testdata" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["tiny-internlm2", "tiny-olmoe"])
def test_logits_match_the_program_forward(name):
    from repro.models.lm import forward_lm
    from repro.models.quantized import unpack_params

    spec = tiny(name)
    cfg = artifact.model_config(spec, {"kv_pool": "bf16"})
    params = artifact.build_params(cfg, spec, 2**31 + 9)
    toks = np.random.default_rng(0).integers(0, spec["vocab_size"], 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = forward_lm(unpack_params(params), {"tokens": jnp.asarray(toks[None])}, cfg,
                         compute_dtype=jnp.float32).logits[0]
    want = reference.Reference(spec, "bf16", 16, 2**31 + 9).logits(toks)
    scale = np.abs(want).max()
    assert scale > 0.5  # logits of a real spread, not a vanishing signal
    assert np.abs(np.asarray(got) - want).max() <= 1e-4 * scale


def test_weights_are_codes_times_a_power_of_two():
    spec = tiny("tiny-olmoe")
    key = weights.root_key(7)
    c, f = weights.layer_codes(key, spec, "moe/experts/up_proj/kernel", 1)
    assert c.dtype == jnp.int8 and set(np.unique(np.asarray(c))) == {-1, 0, 1}
    assert f.shape == (spec["num_experts"],)  # one exponent per expert
    w = weights.layer_weights(key, spec, 1)["moe/experts/up_proj/kernel"]
    assert np.array_equal(np.asarray(w), np.asarray(c) * 2.0 ** -np.asarray(f)[:, None, None])
    again = weights.layer_codes(key, spec, "moe/experts/up_proj/kernel", 1)[0]
    assert np.array_equal(np.asarray(c), np.asarray(again))


@pytest.mark.parametrize("pool", ["int8_fp", "bf16"])
def test_served_tokens_through_the_pool_are_the_reference_argmax(pool):
    """float32 serving through the Scheduler (bucketed or tail prefill, then
    paged decode): every served token is the reference's first choice up
    to rounding, so the reference models the pool as it is written."""
    from repro.serve import Request, ServeConfig, ServeEngine

    spec = tiny("tiny-internlm2")
    cfg = artifact.model_config(spec, {"kv_pool": pool})
    params = artifact.build_params(cfg, spec, 3)
    eng = ServeEngine(cfg, params, max_len=64, compute_dtype=jnp.float32)
    rng = np.random.default_rng(1)
    reqs = [Request(tokens=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=20)
            for n in (5, 17, 30)]
    with jax.default_matmul_precision("highest"):
        comps = eng.serve(reqs, ServeConfig(n_slots=2, block_size=8))
    ref = reference.Reference(spec, pool, 8, 3)
    for r, c in zip(reqs, comps):
        served = np.asarray(c.tokens, np.int32)
        seq = np.concatenate([r.tokens, served[:-1]])
        best, got = ref.score(seq, len(r.tokens) - 1, served)
        assert np.max(best - got) <= 1e-4 * np.abs(best).max()


@pytest.mark.parametrize("chunks", [(27,), (16, 11)])
def test_int8_pool_prefill_logits_match(chunks):
    """Logits after a (chunked) tail prefill that reads every key and value
    back from the int8 pool, against the reference with the pool's
    rounding: the reference models the pool as the program writes it."""
    from repro.models.lm import prefill_prefix_lm
    from repro.serve import Scheduler, ServeConfig, ServeEngine

    spec = tiny("tiny-internlm2")
    cfg = artifact.model_config(spec, {"kv_pool": "int8_fp"})
    params = artifact.build_params(cfg, spec, 5)
    eng = ServeEngine(cfg, params, max_len=64, compute_dtype=jnp.float32)
    caches = Scheduler(eng, ServeConfig(n_slots=1, block_size=8)).caches
    row = jnp.arange(1, 9, dtype=jnp.int32)  # the slot's eight physical blocks
    toks = np.random.default_rng(2).integers(0, 256, sum(chunks)).astype(np.int32)
    start = 0
    with jax.default_matmul_precision("highest"):
        for n in chunks:
            logits, caches = prefill_prefix_lm(
                eng.params, {"tokens": jnp.asarray(toks[None, start:start + n])}, caches, row,
                jnp.int32(start), cfg, seq_len=jnp.int32(n), compute_dtype=jnp.float32)
            start += n
    want = reference.Reference(spec, "int8_fp", 8, 5).logits(toks)[-1]
    got = np.asarray(logits).reshape(-1)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
