"""SYMOG-quantized paged KV pools (DESIGN.md §11).

Two layers of contract:

  - arithmetic: the per-block power-of-two quantizer (``block_scale_exp`` +
    ``quantize_fixed``) bounds its round-trip error by the grid step the
    calibration picked — a hypothesis sweep drives adversarial per-head
    dynamic ranges (heads 2^10 apart in the same block) through int8 AND
    packed int4, and 4-bit ``pack_int``/``unpack_int`` round-trip every
    nibble exactly;
  - serving: on a quantized pool the write-once-read-many discipline makes
    the pool its own oracle — prefix-cache hit vs miss, chunked vs one-shot
    prefill, and serve-twice replays are all BIT-identical streams, because
    every admission routes through the same quantized-pool trace and a
    block's scale is calibrated once, at fill, from its first position.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.packing import pack_int, unpack_int
from repro.models.attention import (
    KV_EXP_MAX,
    KV_EXP_MIN,
    KV_QMAX,
    block_scale_exp,
    quantize_fixed,
)
from repro.models.lm import init_lm
from repro.serve import Request, ServeConfig, ServeEngine

MAX_LEN = 24
_ENGINES = {}


def _engine(dtype):
    if dtype not in _ENGINES:
        cfg = dataclasses.replace(
            configs.get_reduced("internlm2-1.8b"), kv_cache_dtype=dtype
        )
        params = init_lm(jax.random.PRNGKey(0), cfg)
        _ENGINES[dtype] = ServeEngine(cfg, params, max_len=MAX_LEN, compute_dtype=jnp.float32)
    return _ENGINES[dtype]


def _prompt(key, n, vocab):
    return np.asarray(jax.random.randint(key, (n,), 0, vocab), np.int32)


def _tokens(comps):
    return [np.asarray(c.tokens) for c in comps]


# ---------------------------------------------------------------------------
# quantizer arithmetic
# ---------------------------------------------------------------------------
def test_pack_unpack_int4_exact_round_trip():
    """Every (lo, hi) nibble pair survives the split-halves packing."""
    vals = jnp.arange(-8, 8, dtype=jnp.int32)
    lo, hi = jnp.meshgrid(vals, vals, indexing="ij")
    x = jnp.stack([lo.ravel(), hi.ravel()], axis=-1)  # (256, 2): w = 1
    packed = pack_int(x, 4)
    assert packed.dtype == jnp.int8 and packed.shape == (256, 1)
    np.testing.assert_array_equal(np.asarray(unpack_int(packed, 4, 2)), np.asarray(x))


try:
    from hypothesis import given, settings, strategies as st

    _hyp_cases = given(
        st.sampled_from([8, 4]),  # bits
        st.integers(min_value=-10, max_value=10),  # per-head exponent spread
        st.integers(min_value=0, max_value=2**31 - 1),  # data seed
    )

    def _hyp(fn):
        return settings(max_examples=40, deadline=None)(_hyp_cases(fn))
except ImportError:  # pragma: no cover - exercised on minimal installs only

    def _hyp(fn):
        return pytest.mark.skip(reason="hypothesis not installed")(fn)


@_hyp
def test_block_quantize_round_trip_bound(bits, spread, seed):
    """The §3.1 fixed-point contract, per block: with e calibrated from the
    block's first position, that position round-trips within half a grid
    step (Δ/2 = 2^{e-1}), and ANY in-range value |x| ≤ qmax·2^e does too —
    even when two heads in the same block sit 2^{spread} apart, because the
    exponent is per-(block, head)."""
    qmax = KV_QMAX[bits]
    key = jax.random.PRNGKey(seed)
    pool = jax.random.normal(key, (3, 8, 2, 16), jnp.float32)
    # adversarial per-head dynamic range: head 1 scaled 2^spread vs head 0
    pool = pool * jnp.exp2(jnp.array([0.0, float(spread)]))[None, None, :, None]
    e = block_scale_exp(pool[:, 0], qmax)
    assert e.shape == (3, 2) and e.dtype == jnp.int32
    assert bool(jnp.all((e >= KV_EXP_MIN) & (e <= KV_EXP_MAX)))
    q = quantize_fixed(pool, e[:, None], qmax)
    if bits == 4:
        q = unpack_int(pack_int(q, 4), 4, q.shape[-1])  # the pool stores packed words
    deq = q.astype(jnp.float32) * jnp.exp2(e[:, None].astype(jnp.float32))[..., None]
    err = np.abs(np.asarray(deq) - np.asarray(pool))
    step = np.broadcast_to(  # Δ = 2^e, broadcast over (block, pos, head, lane)
        np.exp2(np.asarray(e, np.float32))[:, None, :, None], err.shape
    )
    # calibration position: always in range by construction (amax ≤ qmax/2·Δ)
    assert np.all(err[:, 0] <= 0.5 * step[:, 0] + 1e-7)
    # later positions: the bound holds wherever the value is representable
    in_range = np.abs(np.asarray(pool)) <= qmax * step
    assert np.all(err[in_range] <= (0.5 * step + 1e-7)[in_range])
    # clipped values saturate at the grid edge, never wrap
    assert np.all(np.abs(np.asarray(q)) <= qmax)


# ---------------------------------------------------------------------------
# serving: the quantized pool is its own oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["int8_fp", "int4_fp"])
def test_quantized_serve_twice_deterministic(dtype, rng):
    eng = _engine(dtype)
    assert eng.kv_quant_bits == {"int8_fp": 8, "int4_fp": 4}[dtype]
    reqs = [
        Request(tokens=_prompt(jax.random.fold_in(rng, i), 5 + i, eng.cfg.vocab_size),
                max_new_tokens=6)
        for i in range(3)
    ]
    cfg = ServeConfig(n_slots=2, block_size=4)
    a = _tokens(eng.serve(reqs, cfg))
    b = _tokens(eng.serve(reqs, cfg))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("dtype", ["int8_fp", "int4_fp"])
def test_quantized_prefix_hit_bit_identical(dtype, rng):
    """§11 write-once-read-many: the hit re-reads the miss's quantized
    blocks, and the miss's first token ALSO came from quantized-pool
    attention (misses route through the tail-prefill trace on this tier),
    so hit and miss streams match bit for bit."""
    eng = _engine(dtype)
    prompt = _prompt(rng, 8, eng.cfg.vocab_size)
    reqs = [Request(tokens=prompt, max_new_tokens=6) for _ in range(2)]
    comps, sched = eng.serve(
        reqs, ServeConfig(n_slots=2, block_size=4, prefix_cache=True), return_scheduler=True
    )
    assert sched.stats["prefix_hits"] == 1 and sched.stats["prefix_misses"] == 1
    hit, miss = _tokens(comps)
    np.testing.assert_array_equal(hit, miss)
    # ...and identical to the same workload with sharing disabled
    off = _tokens(eng.serve(reqs, ServeConfig(n_slots=2, block_size=4)))
    np.testing.assert_array_equal(off[0], hit)
    sched.pool.check()


@pytest.mark.parametrize("dtype", ["int8_fp", "int4_fp"])
def test_quantized_speculative_matches_plain(dtype, rng):
    """Speculative decoding over quantized pools: the draft mirror pool
    quantizes with the same per-block discipline, and greedy speculative
    streams equal the plain quantized-pool serve — §8's losslessness
    contract transfers with the pool as its own oracle (draft = the
    target's own params, so every draft is accepted)."""
    from repro.serve import SpeculativeConfig

    eng = _engine(dtype)
    reqs = [
        Request(tokens=_prompt(jax.random.fold_in(rng, 20 + i), 4 + i, eng.cfg.vocab_size),
                max_new_tokens=6)
        for i in range(2)
    ]
    plain = _tokens(eng.serve(reqs, ServeConfig(n_slots=2, block_size=4)))
    spec, sched = eng.serve(
        reqs,
        ServeConfig(n_slots=2, block_size=4,
                    speculative=SpeculativeConfig(draft=eng.params, k=2)),
        return_scheduler=True,
    )
    assert sched.stats["spec_steps"] > 0 and sched.stats["spec_accepted"] > 0
    for a, b in zip(plain, _tokens(spec)):
        np.testing.assert_array_equal(a, b)


def test_int8_chunked_prefill_matches_one_shot(rng):
    """Chunked admission quantizes each chunk into blocks the one-shot path
    fills in a single trace — identical block contents (first-position
    calibration) means identical tokens."""
    eng = _engine("int8_fp")
    reqs = [
        Request(tokens=_prompt(jax.random.fold_in(rng, 9), 11, eng.cfg.vocab_size),
                max_new_tokens=6)
    ]
    one = _tokens(eng.serve(reqs, ServeConfig(n_slots=1, block_size=4)))
    chunked, sched = eng.serve(
        reqs, ServeConfig(n_slots=1, block_size=4, prefill_chunk=4), return_scheduler=True
    )
    assert sched.stats["chunked_admissions"] >= 1
    np.testing.assert_array_equal(one[0], _tokens(chunked)[0])


def test_quantized_pool_leaves_and_scales_allocated():
    """The scheduler's pool really is int8 + int32 scale siblings, with the
    int4 feature axis packed to half width."""
    eng8, eng4 = _engine("int8_fp"), _engine("int4_fp")
    caps = eng8.capabilities()
    assert caps["fully_paged"] and caps["prefix_cache"]
    _, sched = eng8.serve(
        [Request(tokens=np.arange(4, dtype=np.int32), max_new_tokens=2)],
        ServeConfig(n_slots=1, block_size=4),
        return_scheduler=True,
    )
    _, sched4 = eng4.serve(
        [Request(tokens=np.arange(4, dtype=np.int32), max_new_tokens=2)],
        ServeConfig(n_slots=1, block_size=4),
        return_scheduler=True,
    )
    def leaves(sched):
        for sub_pool in sched.caches.values():
            for sub in sub_pool.values():
                yield from sub.items()

    hd = eng8.cfg.head_dim
    n_kv = 0
    for name, leaf in leaves(sched):
        if name.endswith("_scale"):
            assert leaf.dtype == jnp.int32
        elif name in ("k", "v"):
            n_kv += 1
            assert leaf.dtype == jnp.int8 and leaf.shape[-1] == hd
    assert n_kv > 0
    for name, leaf in leaves(sched4):
        if name in ("k", "v"):
            assert leaf.dtype == jnp.int8 and leaf.shape[-1] == hd // 2
