"""Fused paged-attention kernel parity (repro.kernels.paged_attention).

The serving hot path replaces gather → mask → softmax with a Pallas kernel
whose block-table lookup lives inside the online-softmax loop (DESIGN.md
§9).  These tests run the kernel in interpret mode (no TPU) and pin it,
layer by layer, to the composed REFERENCE path (``gather_logical`` + dense
masked softmax) it fuses away:

  - kernel vs pure-jnp oracle across block sizes {8, 16}, GQA/MQA head
    layouts, sliding window + softcap (gemma2), multi-token query rows
    (the verify pass), int8 fixed-point pools and bf16 inputs;
  - the MLA absorbed-decode variant against its oracle;
  - the real layer entry points (attn_decode / attn_verify_paged /
    attn_prefill_paged / mla_decode / mla_verify_paged) under the
    'fused-interpret' backend vs 'composed' — same params, same pools;
  - a hypothesis property test that targets the ``gather_logical``
    reference EXPLICITLY (any table permutation gathers exactly the rows it
    names, from MLA and head-major KV pools);
  - end-to-end: greedy serve() over the fused backend is token-identical
    to ``generate_static`` (which always runs the dense uniform-pos path).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.packing import pack_int
from repro.kernels.dispatch import set_attention_backend
from repro.kernels.paged_attention import paged_attention, paged_attention_mla
from repro.kernels.paged_attention.ref import (
    gather_logical,
    paged_attention_mla_ref,
    paged_attention_ref,
)
from repro.models.attention import (
    KV_F,
    KV_QMAX,
    AttnConfig,
    MLAConfig,
    attn_decode,
    attn_init,
    attn_prefill_paged,
    attn_verify_paged,
    block_scale_exp,
    cache_write,
    mla_decode,
    mla_init,
    mla_verify_paged,
    quantize_fixed,
)

KV_SCALE = 2.0**-KV_F


def _quant_pool(pool, bits):
    """Per-block SYMOG quantization of a float pool, first-position
    calibrated — exactly the serving write path's arithmetic (§11)."""
    qmax = KV_QMAX[bits]
    first = pool[:, :, 0] if pool.ndim == 4 else pool[:, 0]  # head-major KV
    e = block_scale_exp(first, qmax)  # (n_blocks[, K])
    q = quantize_fixed(pool, e[..., None], qmax)
    return (pack_int(q, 4) if bits == 4 else q), e


@pytest.fixture
def fused_interpret():
    """Pin the attention backend to the kernel's interpret path; tests that
    need the composed oracle flip the global themselves mid-test."""
    set_attention_backend("fused-interpret")
    yield
    set_attention_backend("auto")


def _tables(key, B, max_blocks, n_blocks):
    """Per-row tables drawing DISTINCT physical blocks from 1..n_blocks-1
    (0 is the trash block) in a random permutation — the gather really has
    to follow the table, a linear layout would hide index bugs."""
    perm = jax.random.permutation(key, jnp.arange(1, n_blocks))[: B * max_blocks]
    return perm.reshape(B, max_blocks).astype(jnp.int32)


def _case(key, *, B, T, K, G, hd, block, max_blocks, int8=False, dtype=jnp.float32):
    n_blocks = B * max_blocks + 1
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (B, T, K, G, hd), jnp.float32).astype(dtype)
    k_pool = jax.random.normal(ks[1], (n_blocks, K, block, hd), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n_blocks, K, block, hd), jnp.float32)
    bt = _tables(ks[3], B, max_blocks, n_blocks)
    pos_last = jax.random.randint(ks[4], (B,), T - 1, max_blocks * block)
    pos0 = (pos_last - (T - 1)).astype(jnp.int32)
    if int8:
        k_pool = cache_write(k_pool * 0.5, jnp.int8)
        v_pool = cache_write(v_pool * 0.5, jnp.int8)
    else:
        k_pool, v_pool = k_pool.astype(dtype), v_pool.astype(dtype)
    return q, k_pool, v_pool, bt, pos0


def _assert_close(a, b, dtype=jnp.float32):
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), **tol
    )


# ---------------------------------------------------------------------------
# kernel vs oracle: layouts x block sizes x window/softcap x T
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize(
    "layout,T,window,cap",
    [
        ("gqa", 1, None, 0.0),  # plain grouped decode
        ("gqa", 1, 5, 8.0),  # gemma2: sliding window + softcap
        ("mqa", 1, None, 0.0),  # K=1 multi-query
        ("gqa", 4, None, 0.0),  # verify pass: K+1 query rows
        ("gqa", 4, 7, 0.0),  # windowed verify
        ("mha", 3, None, 0.0),  # G=1, every head its own KV
    ],
)
def test_kernel_matches_reference(block, layout, T, window, cap, rng):
    K, G = {"gqa": (2, 2), "mqa": (1, 4), "mha": (4, 1)}[layout]
    q, kp, vp, bt, pos0 = _case(
        jax.random.fold_in(rng, block), B=3, T=T, K=K, G=G, hd=16,
        block=block, max_blocks=3,
    )
    scale = 16**-0.5
    got = paged_attention(
        q, kp, vp, bt, pos0, scale=scale, cap=cap, window=window, interpret=True
    )
    want = paged_attention_ref(q, kp, vp, bt, pos0, scale=scale, cap=cap, window=window)
    _assert_close(got, want)


@pytest.mark.parametrize("block", [8, 16])
def test_kernel_int8_fixed_point_pools(block, rng):
    """2^-KV_F dequantization happens INSIDE the kernel — parity against the
    oracle applying the same exponent shift after its gather."""
    q, kp, vp, bt, pos0 = _case(
        rng, B=2, T=1, K=2, G=2, hd=16, block=block, max_blocks=3, int8=True
    )
    assert kp.dtype == jnp.int8
    got = paged_attention(
        q, kp, vp, bt, pos0, scale=0.25, kv_scale=KV_SCALE, interpret=True
    )
    want = paged_attention_ref(q, kp, vp, bt, pos0, scale=0.25, kv_scale=KV_SCALE)
    _assert_close(got, want)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize(
    "layout,T,window,cap",
    [
        ("gqa", 1, None, 0.0),  # plain grouped decode
        ("gqa", 1, 5, 8.0),  # sliding window + softcap
        ("mqa", 1, None, 0.0),  # K=1 multi-query
        ("gqa", 4, 7, 0.0),  # windowed verify rows
    ],
)
def test_kernel_per_block_quantized_pools(bits, layout, T, window, cap, rng):
    """DESIGN.md §11: per-(block, head) exponent dequantization — and the
    int4 word unpack — happen INSIDE the online-softmax loop.  The oracle
    gets the SAME quantized pool + exponents, so parity is exact to kernel
    tolerance (the quantized pool is its own oracle)."""
    K, G = {"gqa": (2, 2), "mqa": (1, 4)}[layout]
    q, kp, vp, bt, pos0 = _case(
        jax.random.fold_in(rng, bits), B=3, T=T, K=K, G=G, hd=16,
        block=8, max_blocks=3,
    )
    k_q, ke = _quant_pool(kp, bits)
    v_q, ve = _quant_pool(vp, bits)
    assert k_q.dtype == jnp.int8
    assert k_q.shape[-1] == (8 if bits == 4 else 16)
    kw = dict(scale=16**-0.5, cap=cap, window=window,
              k_scale_exp=ke, v_scale_exp=ve, kv_bits=bits)
    got = paged_attention(q, k_q, v_q, bt, pos0, interpret=True, **kw)
    want = paged_attention_ref(q, k_q, v_q, bt, pos0, **kw)
    _assert_close(got, want)


@pytest.mark.parametrize("bits", [8, 4])
def test_mla_kernel_per_block_quantized_pools(bits, rng):
    B, T, H, r, rope, block = 2, 1, 4, 32, 16, 8
    n_blocks = B * 3 + 1
    ks = jax.random.split(rng, 6)
    q_eff = jax.random.normal(ks[0], (B, T, H, r), jnp.float32)
    q_rope = jax.random.normal(ks[1], (B, T, H, rope), jnp.float32)
    ckv = jax.random.normal(ks[2], (n_blocks, block, r), jnp.float32)
    kr = jax.random.normal(ks[3], (n_blocks, block, rope), jnp.float32)
    bt = _tables(ks[4], B, 3, n_blocks)
    pos0 = jax.random.randint(ks[5], (B,), 0, 3 * block).astype(jnp.int32)
    ckv_q, ce = _quant_pool(ckv, bits)
    kr_q, re = _quant_pool(kr, bits)
    kw = dict(scale=0.1, ckv_scale_exp=ce, kr_scale_exp=re, kv_bits=bits)
    got = paged_attention_mla(q_eff, q_rope, ckv_q, kr_q, bt, pos0, interpret=True, **kw)
    want = paged_attention_mla_ref(q_eff, q_rope, ckv_q, kr_q, bt, pos0, **kw)
    _assert_close(got, want)


def test_kernel_bf16_inputs(rng):
    q, kp, vp, bt, pos0 = _case(
        rng, B=2, T=2, K=2, G=2, hd=16, block=8, max_blocks=3, dtype=jnp.bfloat16
    )
    got = paged_attention(q, kp, vp, bt, pos0, scale=0.25, window=6, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = paged_attention_ref(q, kp, vp, bt, pos0, scale=0.25, window=6)
    _assert_close(got, want, jnp.bfloat16)


def test_kernel_traced_window_scalar(rng):
    """One trace must serve any window value (the gemma2/3 scan carries the
    per-layer window as a traced scalar)."""
    q, kp, vp, bt, pos0 = _case(rng, B=2, T=1, K=2, G=2, hd=16, block=8, max_blocks=3)

    @jax.jit
    def run(w):
        return paged_attention(q, kp, vp, bt, pos0, scale=0.25, window=w, interpret=True)

    for w in (3, 9, 2**30):
        want = paged_attention_ref(q, kp, vp, bt, pos0, scale=0.25, window=w)
        _assert_close(run(jnp.int32(w)), want)


@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("T", [1, 3])
def test_mla_kernel_matches_reference(block, T, rng):
    B, H, r, rope = 2, 4, 32, 16
    n_blocks = B * 3 + 1
    ks = jax.random.split(rng, 6)
    q_eff = jax.random.normal(ks[0], (B, T, H, r), jnp.float32)
    q_rope = jax.random.normal(ks[1], (B, T, H, rope), jnp.float32)
    ckv = jax.random.normal(ks[2], (n_blocks, block, r), jnp.float32)
    kr = jax.random.normal(ks[3], (n_blocks, block, rope), jnp.float32)
    bt = _tables(ks[4], B, 3, n_blocks)
    pos0 = jax.random.randint(ks[5], (B,), T - 1, 3 * block) - (T - 1)
    got = paged_attention_mla(
        q_eff, q_rope, ckv, kr, bt, pos0.astype(jnp.int32), scale=0.1, interpret=True
    )
    want = paged_attention_mla_ref(q_eff, q_rope, ckv, kr, bt, pos0, scale=0.1)
    _assert_close(got, want)


# ---------------------------------------------------------------------------
# layer parity: fused-interpret backend vs the composed path, same pools
# ---------------------------------------------------------------------------
def _layer_case(key, cfg, *, B, max_blocks, block, int8=False):
    n_blocks = B * max_blocks + 1
    K, hd = cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    params = attn_init(ks[0], cfg)
    pool_dtype = jnp.int8 if int8 else jnp.float32
    cache = {
        "k": cache_write(
            jax.random.normal(ks[1], (n_blocks, K, block, hd), jnp.float32) * 0.5,
            pool_dtype,
        ),
        "v": cache_write(
            jax.random.normal(ks[2], (n_blocks, K, block, hd), jnp.float32) * 0.5,
            pool_dtype,
        ),
    }
    bt = _tables(ks[3], B, max_blocks, n_blocks)
    return params, cache, bt, ks[4]


def _run_both(fn):
    """Call ``fn()`` under each backend and return (fused, composed)."""
    set_attention_backend("fused-interpret")
    fused = fn()
    set_attention_backend("composed")
    composed = fn()
    return fused, composed


@pytest.mark.parametrize(
    "window,softcap,int8", [(None, 0.0, False), (5, 4.0, False), (None, 0.0, True)]
)
def test_attn_decode_layer_parity(window, softcap, int8, rng, fused_interpret):
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=16, softcap=softcap)
    params, cache, bt, key = _layer_case(rng, cfg, B=3, max_blocks=3, block=8, int8=int8)
    x = jax.random.normal(key, (3, 1, cfg.d_model), jnp.float32)
    pos = jnp.array([5, 13, 2], jnp.int32)

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: attn_decode(
            params, x, cache, pos, cfg=cfg, window=window,
            compute_dtype=jnp.float32, block_tables=bt,
        )
    )
    _assert_close(y_f, y_c)
    # the scatter is backend-independent: caches must be bit-identical
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(c_f[name]), np.asarray(c_c[name]))


def _quantize_cache(cache, names, bits):
    """Convert float pool leaves to SYMOG form: int8/packed-int4 mantissas
    plus the ``<name>_scale`` int32 exponent sibling (§11)."""
    out = dict(cache)
    for name in names:
        out[name], out[name + "_scale"] = _quant_pool(cache[name], bits)
    return out


@pytest.mark.parametrize("bits", [8, 4])
def test_attn_decode_layer_parity_quantized(bits, rng, fused_interpret):
    """Quantized pools at the layer level: the ``k_scale`` sibling routes
    both backends through per-block dequant, and the write path quantizes
    the new token into the pool — scatter AND scale updates bit-identical
    across backends."""
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=16)
    params, cache, bt, key = _layer_case(rng, cfg, B=3, max_blocks=3, block=8)
    cache = _quantize_cache(cache, ("k", "v"), bits)
    x = jax.random.normal(key, (3, 1, cfg.d_model), jnp.float32)
    pos = jnp.array([5, 13, 2], jnp.int32)

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: attn_decode(
            params, x, cache, pos, cfg=cfg, compute_dtype=jnp.float32,
            block_tables=bt,
        )
    )
    _assert_close(y_f, y_c)
    for name in ("k", "v", "k_scale", "v_scale"):
        assert c_f[name].dtype == (jnp.int32 if name.endswith("_scale") else jnp.int8)
        np.testing.assert_array_equal(np.asarray(c_f[name]), np.asarray(c_c[name]))


@pytest.mark.parametrize("bits", [8, 4])
def test_attn_verify_layer_parity_quantized(bits, rng, fused_interpret):
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=16)
    params, cache, bt, key = _layer_case(rng, cfg, B=2, max_blocks=3, block=8)
    cache = _quantize_cache(cache, ("k", "v"), bits)
    T = 4
    x = jax.random.normal(key, (2, T, cfg.d_model), jnp.float32)
    positions = jnp.array([3, 9], jnp.int32)[:, None] + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.array([[True] * 4, [True, True, True, False]])

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: attn_verify_paged(
            params, x, cache, bt, positions, cfg=cfg, valid=valid,
            compute_dtype=jnp.float32,
        )
    )
    _assert_close(y_f, y_c)
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(np.asarray(c_f[name]), np.asarray(c_c[name]))


@pytest.mark.parametrize("bits", [8, 4])
def test_attn_prefill_layer_parity_quantized(bits, rng, fused_interpret):
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=16)
    params, cache, bt, key = _layer_case(rng, cfg, B=1, max_blocks=4, block=8)
    cache = _quantize_cache(cache, ("k", "v"), bits)
    T, seq_len, start = 8, 5, 6
    x = jax.random.normal(key, (1, T, cfg.d_model), jnp.float32)
    positions = (start + jnp.arange(T, dtype=jnp.int32))[None, :]

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: attn_prefill_paged(
            params, x, cache, bt[0], positions, cfg=cfg,
            seq_len=jnp.int32(seq_len), compute_dtype=jnp.float32,
        )
    )
    _assert_close(y_f[:, :seq_len], y_c[:, :seq_len])
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(np.asarray(c_f[name]), np.asarray(c_c[name]))


@pytest.mark.parametrize("window", [None, 6])
def test_attn_verify_layer_parity(window, rng, fused_interpret):
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=16)
    params, cache, bt, key = _layer_case(rng, cfg, B=2, max_blocks=3, block=8)
    T = 4
    x = jax.random.normal(key, (2, T, cfg.d_model), jnp.float32)
    pos0 = jnp.array([3, 9], jnp.int32)
    positions = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    valid = jnp.array([[True] * 4, [True, True, True, False]])

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: attn_verify_paged(
            params, x, cache, bt, positions, cfg=cfg, valid=valid,
            window=window, compute_dtype=jnp.float32,
        )
    )
    _assert_close(y_f, y_c)
    np.testing.assert_array_equal(np.asarray(c_f["k"]), np.asarray(c_c["k"]))


def test_attn_prefill_layer_parity(rng, fused_interpret):
    """Tail prefill: batch-of-one bucket starting at a cached offset; rows
    past ``seq_len`` are trash-redirected garbage on BOTH paths, so parity
    holds on the real rows only."""
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=16)
    params, cache, bt, key = _layer_case(rng, cfg, B=1, max_blocks=4, block=8)
    T, seq_len, start = 8, 5, 6
    x = jax.random.normal(key, (1, T, cfg.d_model), jnp.float32)
    positions = (start + jnp.arange(T, dtype=jnp.int32))[None, :]

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: attn_prefill_paged(
            params, x, cache, bt[0], positions, cfg=cfg,
            seq_len=jnp.int32(seq_len), compute_dtype=jnp.float32,
        )
    )
    _assert_close(y_f[:, :seq_len], y_c[:, :seq_len])
    np.testing.assert_array_equal(np.asarray(c_f["k"]), np.asarray(c_c["k"]))


def _mla_layer_case(key, cfg, *, B, max_blocks, block):
    n_blocks = B * max_blocks + 1
    ks = jax.random.split(key, 5)
    params = mla_init(ks[0], cfg)
    cache = {
        "c_kv": jax.random.normal(ks[1], (n_blocks, block, cfg.kv_lora_rank), jnp.float32),
        "k_rope": jax.random.normal(ks[2], (n_blocks, block, cfg.qk_rope_dim), jnp.float32),
    }
    bt = _tables(ks[3], B, max_blocks, n_blocks)
    return params, cache, bt, ks[4]


def test_mla_decode_layer_parity(rng, fused_interpret):
    cfg = MLAConfig(d_model=32, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
                    qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8)
    params, cache, bt, key = _mla_layer_case(rng, cfg, B=2, max_blocks=3, block=8)
    x = jax.random.normal(key, (2, 1, cfg.d_model), jnp.float32)
    pos = jnp.array([7, 15], jnp.int32)

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: mla_decode(
            params, x, cache, pos, cfg=cfg, compute_dtype=jnp.float32, block_tables=bt
        )
    )
    _assert_close(y_f, y_c)
    np.testing.assert_array_equal(np.asarray(c_f["c_kv"]), np.asarray(c_c["c_kv"]))


@pytest.mark.parametrize("bits", [8, 4])
def test_mla_decode_layer_parity_quantized(bits, rng, fused_interpret):
    cfg = MLAConfig(d_model=32, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
                    qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8)
    params, cache, bt, key = _mla_layer_case(rng, cfg, B=2, max_blocks=3, block=8)
    cache = _quantize_cache(cache, ("c_kv", "k_rope"), bits)
    x = jax.random.normal(key, (2, 1, cfg.d_model), jnp.float32)
    pos = jnp.array([7, 15], jnp.int32)

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: mla_decode(
            params, x, cache, pos, cfg=cfg, compute_dtype=jnp.float32, block_tables=bt
        )
    )
    _assert_close(y_f, y_c)
    for name in ("c_kv", "k_rope", "c_kv_scale", "k_rope_scale"):
        np.testing.assert_array_equal(np.asarray(c_f[name]), np.asarray(c_c[name]))


def test_mla_verify_layer_parity(rng, fused_interpret):
    cfg = MLAConfig(d_model=32, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
                    qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8)
    params, cache, bt, key = _mla_layer_case(rng, cfg, B=2, max_blocks=3, block=8)
    T = 3
    x = jax.random.normal(key, (2, T, cfg.d_model), jnp.float32)
    positions = jnp.array([4, 11], jnp.int32)[:, None] + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.ones((2, T), bool)

    (y_f, c_f), (y_c, c_c) = _run_both(
        lambda: mla_verify_paged(
            params, x, cache, bt, positions, cfg=cfg, valid=valid,
            compute_dtype=jnp.float32,
        )
    )
    _assert_close(y_f, y_c)
    np.testing.assert_array_equal(np.asarray(c_f["c_kv"]), np.asarray(c_c["c_kv"]))


# ---------------------------------------------------------------------------
# property test: the gather_logical REFERENCE itself (the oracle the kernel is
# pinned to) — any table gathers exactly the physical rows it names.
# Guarded like test_blockpool.py so minimal installs still run the rest.
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st

    _hyp_cases = given(
        st.integers(min_value=1, max_value=4),  # B
        st.integers(min_value=1, max_value=4),  # max_blocks
        st.sampled_from([4, 8]),  # block
        st.integers(min_value=0, max_value=2**31 - 1),  # table seed
    )

    def _hyp(fn):
        return settings(max_examples=40, deadline=None)(_hyp_cases(fn))
except ImportError:  # pragma: no cover - exercised on minimal installs only

    def _hyp(fn):
        return pytest.mark.skip(reason="hypothesis not installed")(fn)


@_hyp
def test_paged_gather_reference_property(B, max_blocks, block, seed):
    """gather_logical's entry (b, j*block + t) is EXACTLY token t of
    physical block tables[b, j] — for an MLA (n, block, r) pool and a
    head-major KV (n, K, block, hd) pool — with repeated and trash blocks
    allowed, as the scheduler's tables produce them."""
    n_blocks = max_blocks * B + 1
    key = jax.random.PRNGKey(seed)
    bt = jax.random.randint(jax.random.fold_in(key, 1), (B, max_blocks), 0, n_blocks)
    bt_np = np.asarray(bt)
    for shape in ((n_blocks, block, 3), (n_blocks, 2, block, 3)):
        pool = jax.random.normal(jax.random.fold_in(key, 0), shape, jnp.float32)
        got = np.asarray(gather_logical(pool, bt.astype(jnp.int32)))
        tokens = np.asarray(pool)
        if len(shape) == 4:  # token-major view of the head-major blocks
            tokens = tokens.swapaxes(1, 2)
        assert got.shape == (B, max_blocks * block) + tokens.shape[2:]
        for b in range(B):
            for j in range(max_blocks):
                np.testing.assert_array_equal(
                    got[b, j * block : (j + 1) * block], tokens[bt_np[b, j]]
                )


# ---------------------------------------------------------------------------
# end-to-end: greedy serve() over the fused backend == generate_static
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma2-27b"])
def test_serve_fused_token_identical_to_static(arch, rng):
    """The §9 acceptance bar: the engine pins 'fused-interpret' at
    construction and every serve() token matches the static dense-cache
    loop — internlm2 (GQA) and gemma2 (sliding window + softcap + scan-
    traced window scalar)."""
    from repro import configs
    from repro.models import init_lm
    from repro.serve import Request, ServeConfig, ServeEngine

    cfg = configs.get_reduced(arch)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    reqs = [
        Request(
            tokens=np.asarray(
                jax.random.randint(jax.random.fold_in(rng, i), (L,), 0, cfg.vocab_size)
            ),
            max_new_tokens=b,
        )
        for i, (L, b) in enumerate(zip((3, 6, 4), (5, 3, 6)))
    ]
    set_attention_backend("fused-interpret")
    try:
        eng = ServeEngine(cfg, params, max_len=24, compute_dtype=jnp.float32)
        assert eng.attn_backend == "fused-interpret"
        comps = eng.serve(reqs, ServeConfig(n_slots=2))
    finally:
        set_attention_backend("auto")
    for req, comp in zip(reqs, comps):
        static = np.asarray(
            eng.generate_static(
                {"tokens": jnp.asarray(np.asarray(req.tokens)[None])}, req.max_new_tokens
            )
        )[0]
        np.testing.assert_array_equal(np.asarray(comp.tokens), static)
