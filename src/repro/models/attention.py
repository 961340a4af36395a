"""Attention blocks: GQA/MQA self-attention (RoPE, sliding windows, logit
softcap, qk-norm), cross-attention (whisper), and MLA (deepseek-v3) with
compressed-KV decode (matmul absorption).

Shapes: x (B, T, D); q (B, T, H, hd); k/v (B, S, K, hd) with H = K·G.

Long sequences never materialize the full (T, S) score matrix: queries are
processed in chunks of ``q_chunk`` via lax.scan (exact — softmax is
per-query over the full S), which bounds transient memory at
O(B·H·q_chunk·S) per layer.  Masks are built from positions inside the
chunk loop; ``window`` may be a *traced* per-layer scalar (gemma2/3
local/global alternation inside one scan body).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.packing import pack_int, unpack_int
from repro.kernels.dispatch import resolve_attention_backend
from repro.kernels.paged_attention import paged_attention, paged_attention_mla
from repro.kernels.paged_attention.ref import gather_logical
from repro.models.layers import (
    apply_rope,
    dense_apply,
    dense_init,
    rmsnorm_apply,
    rmsnorm_init,
    softcap as softcap_fn,
)
from repro.models.quantized import as_dense

Q_CHUNK_DEFAULT = 1024  # chunk queries when T exceeds this

# ---------------------------------------------------------------------------
# fixed-point KV cache (beyond-paper: the paper's §3.1 quantizer applied to
# the decode-dominant resident bytes).  Two regimes:
#   - DENSE/ring caches: one global power-of-two scale Δ=2^-KV_F — the
#     dequantize is an exponent add, exact, no calibration state.
#   - PAGED pools (DESIGN.md §11): per-block, per-head SYMOG scales.  Each
#     physical block carries an int32 exponent in a ``<leaf>_scale`` sibling
#     leaf, calibrated once from the k/v vector at the block's first slot
#     and never re-rounded (write-once-read-many), so hit/miss/chunked
#     traces stay bit-identical.  int4 packs two lanes per int8 word
#     (core.packing split planes: low nibbles = lanes [0, w/2), high =
#     [w/2, w) within each 128-word chunk).
# ---------------------------------------------------------------------------
KV_F = 5  # Δ = 2^-5: int8 range ±3.97, resolution 1/32 (post-norm k/v ~O(1))

KV_QMAX = {8: 127, 4: 7}  # symmetric mantissa range per wordlength
KV_EXP_MIN, KV_EXP_MAX = -20, 20  # sane exponent clamp (2^±20 stays finite)


def cache_write(x, like_dtype):
    """Quantize a new cache entry when the cache is int8 fixed-point."""
    if like_dtype == jnp.int8:
        scaled = jnp.round(x.astype(jnp.float32) * (2.0**KV_F))
        return jnp.clip(scaled, -127, 127).astype(jnp.int8)
    return x.astype(like_dtype)


def cache_read(c, dtype):
    """Dequantize cache contents (exponent-shift scale)."""
    if c.dtype == jnp.int8:
        return (c.astype(dtype) * jnp.asarray(2.0 ** -KV_F, dtype))
    return c.astype(dtype)


def block_scale_exp(new, qmax):
    """Per-entry SYMOG exponent: smallest e with amax/2^e ≤ qmax/2.

    ``new`` (N, ..., width) float; the amax runs over the feature axis, so
    the result (N, ...) is per KV head where the entry carries a head axis.
    The extra margin bit (+1) leaves factor-2 headroom for the block's
    later tokens, which the calibration entry never sees."""
    amax = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 2.0**-30)) + 1.0 - math.log2(qmax))
    return jnp.clip(e, KV_EXP_MIN, KV_EXP_MAX).astype(jnp.int32)


def quantize_fixed(x, e, qmax):
    """Round x to int8 mantissas under per-entry exponents ``e`` (broadcast
    over the trailing feature axis)."""
    scale = jnp.exp2(-e.astype(jnp.float32))[..., None]
    q = jnp.round(x.astype(jnp.float32) * scale)
    return jnp.clip(q, -qmax, qmax).astype(jnp.int8)


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope: bool = True
    qk_norm: bool = False
    softcap: float = 0.0
    bias: bool = False
    query_scale: Optional[float] = None  # default hd^-0.5


def attn_init(key, cfg: AttnConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(cfg.d_model)
    p = {
        "q_proj": dense_init(
            ks[0],
            (cfg.d_model,),
            (cfg.n_heads, cfg.head_dim),
            bias=cfg.bias,
            stddev=std,
            dtype=dtype,
        ),
        "k_proj": dense_init(
            ks[1],
            (cfg.d_model,),
            (cfg.n_kv_heads, cfg.head_dim),
            bias=cfg.bias,
            stddev=std,
            dtype=dtype,
        ),
        "v_proj": dense_init(
            ks[2],
            (cfg.d_model,),
            (cfg.n_kv_heads, cfg.head_dim),
            bias=cfg.bias,
            stddev=std,
            dtype=dtype,
        ),
        "o_proj": dense_init(
            ks[3],
            (cfg.n_heads, cfg.head_dim),
            (cfg.d_model,),
            bias=cfg.bias,
            stddev=1.0 / math.sqrt(cfg.n_heads * cfg.head_dim),
            dtype=dtype,
        ),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim, dtype)
        p["k_norm"] = rmsnorm_init(cfg.head_dim, dtype)
    return p


def make_mask(q_pos: jax.Array, kv_pos: jax.Array, *, causal: bool = True,
              window=None, prefix_len: int = 0,
              kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """Boolean mask (..., T, S) from query/key positions (traced window ok)."""
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    if causal:
        m = k <= q
    else:
        m = jnp.broadcast_to(jnp.asarray(True), jnp.broadcast_shapes(q.shape, k.shape))
    if window is not None:
        m = m & (q - k < window)
    if prefix_len:
        m = m | (k < prefix_len)
    if kv_valid is not None:
        m = m & kv_valid[..., None, :]
    return m


def _qk_attn(q, k, v, mask, *, scale: float, cap: float) -> jax.Array:
    """q (B,T,K,G,hd), k/v (B,S,K,hd), mask (B,T,S) -> out (B,T,K,G,hd)."""
    logits = jnp.einsum("BTKGh,BSKh->BKGTS", q, k).astype(jnp.float32) * scale
    if cap > 0:
        logits = softcap_fn(logits, cap)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("BKGTS,BSKh->BTKGh", probs.astype(v.dtype), v)
    return out


def attend(q, k, v, q_pos, kv_pos, *, causal=True, window=None, prefix_len=0,
           kv_valid=None, scale: float, cap: float, q_chunk: int = Q_CHUNK_DEFAULT):
    """Exact attention, query-chunked when T > q_chunk.

    q (B,T,K,G,hd); k/v (B,S,K,hd); q_pos (B,T); kv_pos (B,S) or (S,).
    """
    B, T = q.shape[0], q.shape[1]
    if kv_pos.ndim == 1:
        kv_pos = jnp.broadcast_to(kv_pos[None, :], (B, kv_pos.shape[0]))
    if q_chunk <= 0 or T <= q_chunk or T % q_chunk != 0:
        mask = make_mask(q_pos, kv_pos, causal=causal, window=window,
                         prefix_len=prefix_len, kv_valid=kv_valid)
        return _qk_attn(q, k, v, mask, scale=scale, cap=cap)

    nc = T // q_chunk
    qc = jnp.moveaxis(q.reshape(B, nc, q_chunk, *q.shape[2:]), 1, 0)
    pc = jnp.moveaxis(q_pos.reshape(B, nc, q_chunk), 1, 0)

    def body(carry, inp):
        q_i, p_i = inp
        mask = make_mask(p_i, kv_pos, causal=causal, window=window,
                         prefix_len=prefix_len, kv_valid=kv_valid)
        return carry, _qk_attn(q_i, k, v, mask, scale=scale, cap=cap)

    _, out = jax.lax.scan(body, None, (qc, pc))
    out = jnp.moveaxis(out, 0, 1)  # (B, nc, q_chunk, K, G, hd_v)
    return out.reshape(B, T, *out.shape[3:])


def attn_apply(p, x, *, cfg: AttnConfig, positions, kv_positions=None,
               causal=True, window=None, prefix_len: int = 0,
               rope_base=10000.0, compute_dtype=jnp.bfloat16,
               kv: Optional[Tuple[jax.Array, jax.Array]] = None,
               q_chunk: int = Q_CHUNK_DEFAULT):
    """Full-sequence attention.  ``kv``: precomputed (k, v) for cross-attn."""
    B, T, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    q = dense_apply(p["q_proj"], x, compute_dtype=compute_dtype)  # (B,T,H,hd)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q)
    if kv is None:
        k = dense_apply(p["k_proj"], x, compute_dtype=compute_dtype)
        v = dense_apply(p["v_proj"], x, compute_dtype=compute_dtype)
        if cfg.qk_norm:
            k = rmsnorm_apply(p["k_norm"], k)
        if cfg.rope:
            q = apply_rope(q, positions, rope_base)
            k = apply_rope(k, positions, rope_base)
        kv_pos = positions
    else:
        k, v = kv
        if cfg.rope:
            q = apply_rope(q, positions, rope_base)
        S = k.shape[1]
        kv_pos = kv_positions if kv_positions is not None else jnp.arange(S, dtype=jnp.int32)
    q = q.reshape(B, T, K, G, hd)
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5
    out = attend(q, k.astype(compute_dtype), v.astype(compute_dtype),
                 positions, kv_pos, causal=causal and kv is None, window=window,
                 prefix_len=prefix_len, scale=scale, cap=cfg.softcap, q_chunk=q_chunk)
    out = out.reshape(B, T, H, hd)
    return dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)


def attn_init_cache(batch: int, max_len: int, cfg: AttnConfig, dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_positions(pos, batch: int) -> Tuple[jax.Array, bool]:
    """Normalize a decode position argument to (B, 1) int32.

    ``pos`` may be a scalar (uniform batch — the classic generate loop) or a
    (B,) vector (continuous batching: every request sits at its own offset).
    Returns (positions, per_row) where ``per_row`` is a static flag choosing
    between the single-slice cache write and the per-row scatter."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.full((batch, 1), pos, jnp.int32), False
    return pos[:, None], True


def cache_update_rows(cache_leaf, new, pos, *, per_row: bool, axis: int = 1):
    """Write a one-step cache entry at per-row positions.

    cache_leaf (B, S, ...); new (B, 1, ...); pos scalar or (B,).  The uniform
    case keeps the cheap single dynamic_update_slice; the ragged case scatters
    each row at its own offset (vmapped dynamic_update_slice)."""
    new = cache_write(new, cache_leaf.dtype)
    if not per_row:
        return jax.lax.dynamic_update_slice_in_dim(cache_leaf, new, pos, axis)
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis - 1)
    )(cache_leaf, new, pos)


# ---------------------------------------------------------------------------
# paged KV cache: an (n_blocks, ...) pool of token blocks shared by every
# slot, resolved through per-slot block tables (DESIGN.md §6).  KV pools are
# head-major inside a block, (n_blocks, K, block, hd), so each (block, hd)
# is one whole tile of the fused kernel (§9); MLA rank-space pools are
# (n_blocks, block, r).  Indices below are FLAT token indices
# block_id·block + offset.  Block 0 is a reserved trash block: evicted
# slots' tables are zeroed host-side, so their per-step writes land in
# trash instead of needing a revert pass over the pool.
# ---------------------------------------------------------------------------
def paged_pool_shape(lead, n_phys: int, block: int, feat) -> Tuple[int, ...]:
    """Shape of a paged pool leaf holding ``n_phys`` blocks of ``block``
    tokens whose per-token entry is ``feat`` ((K, hd) or (r,)), after
    ``lead`` leading (layer) dims."""
    feat = tuple(feat)
    if len(feat) == 2:
        return tuple(lead) + (n_phys, feat[0], block, feat[1])
    return tuple(lead) + (n_phys, block) + feat


def pool_block(pool, axis: int = 0) -> int:
    """Tokens per block of a paged pool leaf with ``axis`` leading dims."""
    return pool.shape[axis + 2] if pool.ndim - axis == 4 else pool.shape[axis + 1]


def paged_token_index(block_tables, pos, block: int):
    """Flat pool index of each row's write position.

    block_tables (B, max_blocks) physical block ids; pos (B,) int32 logical
    positions.  Returns (B,) flat token indices (block_id·block + offset)."""
    b = jnp.arange(pos.shape[0], dtype=jnp.int32)
    return block_tables[b, pos // block] * block + pos % block


def _pool_set(pool, idx, entries):
    """Write token entries (N, *feat) at flat token indices idx (N,)."""
    block = pool_block(pool)
    bid, off = idx // block, idx % block
    if pool.ndim == 4:  # head-major: the (bid, off) pair selects (K, hd)
        return pool.at[bid, :, off].set(entries)
    return pool.at[bid, off].set(entries)


def paged_update(pool, new, idx):
    """Scatter one decode step into the pool.  new (B, ...) one entry per
    row; idx (B,) flat token indices (rows own disjoint blocks, so only
    trash indices may collide — garbage either way)."""
    return _pool_set(pool, idx, cache_write(new, pool.dtype))


def _pool_dequant_scale(pool) -> float:
    """Static in-kernel dequantization scale for a paged pool leaf."""
    return 2.0 ** -KV_F if pool.dtype == jnp.int8 else 1.0


def paged_quant_update(pool, exp_leaf, new, idx):
    """Scatter entries into a SYMOG-quantized pool (DESIGN.md §11).

    pool (n_blocks, [K,] block, w) int8 mantissa words; exp_leaf (n_blocks,
    [K]) int32 per-block exponents; new (N, ..., width) float entries; idx
    (N,) flat token indices.  A block's exponent is calibrated ONCE, from
    the entry at its first slot (idx % block == 0) — non-start entries
    scatter their candidate exponent into the trash row instead, so a later
    chunk/tail/verify write never re-rounds KV an earlier pass committed.
    The exponent is a pure function of (params, token, position), which is
    what keeps hit, miss and chunked traces bit-identical."""
    block = pool_block(pool)
    bits = 4 if pool.shape[-1] * 2 == new.shape[-1] else 8
    qmax = KV_QMAX[bits]
    bid = idx // block
    tgt = jnp.where(idx % block == 0, bid, 0)  # non-start exponents -> trash
    exp_leaf = exp_leaf.at[tgt].set(block_scale_exp(new, qmax))
    q = quantize_fixed(new, exp_leaf[bid], qmax)
    if bits == 4:
        q = pack_int(q, 4)
    return _pool_set(pool, idx, q), exp_leaf


def _paged_write(cache, names, news, idx):
    """Dict-preserving scatter into paged leaves: leaves with a
    ``<name>_scale`` sibling quantize at write with the block's scale
    (``paged_quant_update``); everything else keeps ``paged_update``.
    ``news`` are flat (N, ...) entries matching ``idx`` (N,)."""
    out = dict(cache)
    for name, new in zip(names, news):
        sname = name + "_scale"
        if sname in cache:
            out[name], out[sname] = paged_quant_update(
                cache[name], cache[sname], new, idx
            )
        else:
            out[name] = paged_update(cache[name], new, idx)
    return out


def _paged_read(cache, name, block_tables, dtype, width):
    """Composed-path gather + dequantize of one paged leaf.

    Per-block-scale leaves unpack int4 words (pool last dim w = width/2)
    and scale every row of physical block p by 2^exp[p] (per head where the
    exponent leaf carries one); KV_F/float leaves keep ``cache_read``."""
    sname = name + "_scale"
    if sname not in cache:
        return cache_read(gather_logical(cache[name], block_tables), dtype)
    data = gather_logical(cache[name], block_tables)
    if cache[name].shape[-1] * 2 == width:
        data = unpack_int(data, 4, width)
    block = pool_block(cache[name])
    e = jnp.repeat(cache[sname][block_tables], block, axis=1)  # (B, S[, K])
    scale = jnp.exp2(e.astype(jnp.float32))[..., None]
    return (data.astype(jnp.float32) * scale).astype(dtype)


def _fused_paged_attn(q, cache, block_tables, positions, *, cfg, window,
                      backend, compute_dtype):
    """Fused-kernel replacement for gather → mask → ``_qk_attn`` over a
    scattered paged pool.  q (B, T, H, hd) post-rope; positions (B, T)
    contiguous per row (the kernel only needs positions[:, 0])."""
    B, T = q.shape[:2]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    scale = cfg.query_scale if cfg.query_scale is not None else hd**-0.5
    quant = "k_scale" in cache
    out = paged_attention(
        q.reshape(B, T, K, H // K, hd),
        cache["k"], cache["v"], block_tables, positions[:, 0],
        scale=scale, cap=cfg.softcap, window=window,
        kv_scale=_pool_dequant_scale(cache["k"]),
        k_scale_exp=cache.get("k_scale"), v_scale_exp=cache.get("v_scale"),
        kv_bits=(4 if cache["k"].shape[-1] * 2 == hd else 8) if quant else 0,
        interpret=backend == "fused-interpret", out_dtype=compute_dtype,
    )
    return out.reshape(B, T, H, hd)


def _fused_paged_mla(q_eff, q_rope, cache, block_tables, positions, *, cfg,
                     backend, compute_dtype):
    """Fused absorbed-MLA decode over the compressed c_kv/k_rope pools.
    Returns the rank-space (B, T, H, r) output — callers still apply the
    kv_b_v expansion."""
    quant = "c_kv_scale" in cache
    kv_bits = 0
    if quant:
        kv_bits = 4 if cache["c_kv"].shape[-1] * 2 == q_eff.shape[-1] else 8
    return paged_attention_mla(
        q_eff, q_rope, cache["c_kv"], cache["k_rope"], block_tables,
        positions[:, 0], scale=_mla_scale(cfg),
        kv_scale=_pool_dequant_scale(cache["c_kv"]),
        ckv_scale_exp=cache.get("c_kv_scale"),
        kr_scale_exp=cache.get("k_rope_scale"), kv_bits=kv_bits,
        interpret=backend == "fused-interpret", out_dtype=compute_dtype,
    )


def attn_prefill_paged(
    p,
    x,
    cache,
    bt_row,
    positions,
    *,
    cfg: AttnConfig,
    seq_len,
    window=None,
    rope_base=10000.0,
    compute_dtype=jnp.bfloat16,
):
    """Prefix-cache tail prefill (DESIGN.md §7): attend a batch-of-one tail
    bucket against the paged pool, starting at a traced offset.

    x (1, T, D) is the right-padded TAIL of a prompt whose first
    ``positions[0, 0]`` tokens are already cached in the pool blocks named
    by ``bt_row``; ``seq_len`` (traced) is the real tail length.  Each real
    tail token writes its k/v into the pool at its global position first
    (rows past ``seq_len`` are redirected to the trash block), THEN the
    layer gathers the whole table row — so every position inside a query's
    causal horizon reads real KV (cached prefix or just-written tail) and
    junk only ever sits beyond it, exactly like decode.  With the pool
    storing at compute dtype this is bit-identical to the full-prompt
    prefill the miss path runs (`tests/test_prefix_cache.py`).

    The traced offset makes this the CHUNK primitive too (DESIGN.md §10):
    chunked prefill calls it once per chunk with ``positions`` starting at
    the tokens already resident (0 included), interleaved with decode
    steps — scatter-before-gather at global positions is exactly what
    makes a chunk see every earlier chunk's KV as if prefilled at once."""
    B, T, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    q = dense_apply(p["q_proj"], x, compute_dtype=compute_dtype)
    k_new = dense_apply(p["k_proj"], x, compute_dtype=compute_dtype)
    v_new = dense_apply(p["v_proj"], x, compute_dtype=compute_dtype)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q)
        k_new = rmsnorm_apply(p["k_norm"], k_new)
    if cfg.rope:
        q = apply_rope(q, positions, rope_base)
        k_new = apply_rope(k_new, positions, rope_base)
    block = pool_block(cache["k"])
    pos_t = positions[0]  # (T,) global positions of the tail bucket
    idx = bt_row[pos_t // block] * block + pos_t % block
    idx = jnp.where(jnp.arange(T, dtype=jnp.int32) < seq_len, idx, 0)  # pads -> trash
    cache = _paged_write(cache, ("k", "v"), (k_new[0], v_new[0]), idx)
    backend = resolve_attention_backend()
    if backend != "composed":
        out = _fused_paged_attn(
            q, cache, bt_row[None], positions, cfg=cfg, window=window,
            backend=backend, compute_dtype=compute_dtype,
        )
        y = dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)
        return y, cache
    k = _paged_read(cache, "k", bt_row[None], compute_dtype, hd)
    v = _paged_read(cache, "v", bt_row[None], compute_dtype, hd)
    S = k.shape[1]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    mask = make_mask(positions, kv_pos[None, :], causal=True, window=window)
    q = q.reshape(B, T, K, G, hd)
    scale = cfg.query_scale if cfg.query_scale is not None else hd**-0.5
    out = _qk_attn(q, k, v, mask, scale=scale, cap=cfg.softcap)
    out = out.reshape(B, T, H, hd)
    y = dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)
    return y, cache


def verify_token_index(block_tables, positions, block: int, valid):
    """Flat pool indices for a (B, T) grid of speculative write positions.

    Generalizes ``paged_token_index`` to T tokens per row: entry (b, t)
    addresses global position ``positions[b, t]`` through row b's table.
    ``valid`` (B, T) bool redirects out-of-range or inactive positions to
    the trash block (physical row 0) BEFORE the table lookup, so a row near
    ``max_len`` can ride a fixed-width verify trace without reading past
    its table (DESIGN.md §8)."""
    B, max_blocks = block_tables.shape
    bi = jnp.minimum(positions // block, max_blocks - 1)  # clamp BEFORE gather
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    idx = block_tables[rows, bi] * block + positions % block
    return jnp.where(valid, idx, 0)


def _verify_scatter(cache, names, news, idx):
    """Scatter (B, T, ...) new entries into each paged leaf at flat ``idx``.
    Rows own disjoint blocks and positions within a row are distinct, so
    only trash-redirected indices may collide (garbage either way)."""
    B, T = idx.shape
    news = [new.reshape((B * T,) + new.shape[2:]) for new in news]
    return _paged_write(cache, names, news, idx.reshape(B * T))


def attn_verify_paged(
    p,
    x,
    cache,
    block_tables,
    positions,
    *,
    cfg: AttnConfig,
    valid,
    window=None,
    rope_base=10000.0,
    compute_dtype=jnp.bfloat16,
):
    """Speculative multi-token verify against the paged pool (DESIGN.md §8).

    x (B, T, D) embeds [last committed token, draft d_1..d_{T-1}] per row;
    ``positions`` (B, T) are the global cache positions ``pos[b] + t`` and
    ``valid`` (B, T) masks inactive rows / positions past ``max_len`` into
    the trash block.  Generalizes the decode step (T=1) and the prefix-
    cache tail prefill (batch-of-one) to B rows x T tokens: every row
    scatters its T k/v entries at its global positions FIRST, then gathers
    its whole table view, so each query's causal horizon reads only real
    KV (committed prefix below ``positions[b, 0]``, own speculated tokens
    at/above it) and the logits at every valid position are exactly what T
    sequential decode steps would have produced."""
    B, T, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    q = dense_apply(p["q_proj"], x, compute_dtype=compute_dtype)
    k_new = dense_apply(p["k_proj"], x, compute_dtype=compute_dtype)
    v_new = dense_apply(p["v_proj"], x, compute_dtype=compute_dtype)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q)
        k_new = rmsnorm_apply(p["k_norm"], k_new)
    if cfg.rope:
        q = apply_rope(q, positions, rope_base)
        k_new = apply_rope(k_new, positions, rope_base)
    idx = verify_token_index(block_tables, positions, pool_block(cache["k"]), valid)
    cache = _verify_scatter(cache, ("k", "v"), (k_new, v_new), idx)
    backend = resolve_attention_backend()
    if backend != "composed":
        out = _fused_paged_attn(
            q, cache, block_tables, positions, cfg=cfg, window=window,
            backend=backend, compute_dtype=compute_dtype,
        )
        return dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype), cache
    k = _paged_read(cache, "k", block_tables, compute_dtype, hd)
    v = _paged_read(cache, "v", block_tables, compute_dtype, hd)
    S = k.shape[1]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    mask = make_mask(positions, kv_pos[None, :], causal=True, window=window)
    q = q.reshape(B, T, K, G, hd)
    scale = cfg.query_scale if cfg.query_scale is not None else hd**-0.5
    out = _qk_attn(q, k, v, mask, scale=scale, cap=cfg.softcap)
    out = out.reshape(B, T, H, hd)
    return dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype), cache


def attn_decode(p, x, cache, pos, *, cfg: AttnConfig, window=None, rope_base=10000.0,
                compute_dtype=jnp.bfloat16,
                kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                block_tables: Optional[jax.Array] = None):
    """Single-token decode.  x (B,1,D); ``pos`` scalar int32 (uniform batch)
    or (B,) int32 (per-request positions — continuous batching).

    Self-attn: writes each row's new k/v at its own ``pos`` and attends to
    cache[0..pos] per row.  Cross-attn (``kv`` given): attends to the fixed
    encoder context.  ``block_tables`` (B, max_blocks) switches the cache to
    the paged layout: ``cache`` leaves are (n_blocks, block, ...) pools, row
    b resolves pos[b] through its table row (scatter the new entry, gather
    its logical view) — requires a (B,) ``pos``.
    """
    B, T, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    q = dense_apply(p["q_proj"], x, compute_dtype=compute_dtype)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q)
    positions, per_row = decode_positions(pos, B)
    if kv is None:
        k_new = dense_apply(p["k_proj"], x, compute_dtype=compute_dtype)
        v_new = dense_apply(p["v_proj"], x, compute_dtype=compute_dtype)
        if cfg.qk_norm:
            k_new = rmsnorm_apply(p["k_norm"], k_new)
        if cfg.rope:
            q = apply_rope(q, positions, rope_base)
            k_new = apply_rope(k_new, positions, rope_base)
        if block_tables is not None:
            if not per_row:
                raise ValueError("paged decode requires per-row (B,) positions")
            idx = paged_token_index(block_tables, positions[:, 0], pool_block(cache["k"]))
            cache = _paged_write(cache, ("k", "v"), (k_new[:, 0], v_new[:, 0]), idx)
            backend = resolve_attention_backend()
            if backend != "composed":
                out = _fused_paged_attn(
                    q, cache, block_tables, positions, cfg=cfg, window=window,
                    backend=backend, compute_dtype=compute_dtype,
                )
                y = dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)
                return y, cache
            k = _paged_read(cache, "k", block_tables, compute_dtype, hd)
            v = _paged_read(cache, "v", block_tables, compute_dtype, hd)
        else:
            cache = {
                "k": cache_update_rows(cache["k"], k_new, pos, per_row=per_row),
                "v": cache_update_rows(cache["v"], v_new, pos, per_row=per_row),
            }
            k, v = cache_read(cache["k"], compute_dtype), cache_read(cache["v"], compute_dtype)
        S = k.shape[1]
        kv_pos = jnp.arange(S, dtype=jnp.int32)
        mask = make_mask(positions, kv_pos[None, :], causal=True, window=window)
        mask = jnp.broadcast_to(mask, (B, 1, S))
    else:
        if cfg.rope:
            q = apply_rope(q, positions, rope_base)
        k, v = kv
        S = k.shape[1]
        mask = jnp.ones((B, 1, S), bool)
    q = q.reshape(B, 1, K, G, hd)
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5
    out = _qk_attn(
        q, k.astype(compute_dtype), v.astype(compute_dtype), mask, scale=scale, cap=cfg.softcap
    )
    out = out.reshape(B, 1, H, hd)
    y = dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)
    return y, cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek-v3)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


def mla_init(key, cfg: MLAConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    D, H = cfg.d_model, cfg.n_heads
    r = cfg
    sd = lambda fan: 1.0 / math.sqrt(fan)
    return {
        "q_a_proj": dense_init(ks[0], (D,), (r.q_lora_rank,), stddev=sd(D), dtype=dtype),
        "q_a_norm": rmsnorm_init(r.q_lora_rank, dtype),
        "q_b_proj": dense_init(
            ks[1],
            (r.q_lora_rank,),
            (H, r.qk_nope_dim + r.qk_rope_dim),
            stddev=sd(r.q_lora_rank),
            dtype=dtype,
        ),
        "kv_a_proj": dense_init(ks[2], (D,), (r.kv_lora_rank,), stddev=sd(D), dtype=dtype),
        "kv_a_norm": rmsnorm_init(r.kv_lora_rank, dtype),
        "k_rope_proj": dense_init(ks[3], (D,), (r.qk_rope_dim,), stddev=sd(D), dtype=dtype),
        "kv_b_k_proj": dense_init(
            ks[4], (r.kv_lora_rank,), (H, r.qk_nope_dim), stddev=sd(r.kv_lora_rank), dtype=dtype
        ),
        "kv_b_v_proj": dense_init(
            ks[5], (r.kv_lora_rank,), (H, r.v_head_dim), stddev=sd(r.kv_lora_rank), dtype=dtype
        ),
        "o_proj": dense_init(
            ks[6], (H, r.v_head_dim), (D,), stddev=sd(H * r.v_head_dim), dtype=dtype
        ),
    }


def _mla_scale(cfg: MLAConfig) -> float:
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5


def mla_apply(p, x, *, cfg: MLAConfig, positions, causal=True, window=None,
              prefix_len: int = 0, rope_base=10000.0,
              compute_dtype=jnp.bfloat16, q_chunk: int = Q_CHUNK_DEFAULT):
    """Full-sequence MLA (train / prefill): expanded-KV form, query-chunked."""
    B, T, D = x.shape
    H = cfg.n_heads
    cq = rmsnorm_apply(p["q_a_norm"], dense_apply(p["q_a_proj"], x, compute_dtype=compute_dtype))
    q = dense_apply(p["q_b_proj"], cq, compute_dtype=compute_dtype)  # (B,T,H,nope+rope)
    q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, rope_base)

    c_kv = rmsnorm_apply(
        p["kv_a_norm"], dense_apply(p["kv_a_proj"], x, compute_dtype=compute_dtype)
    )  # (B,T,r)
    k_rope = dense_apply(p["k_rope_proj"], x, compute_dtype=compute_dtype)[
        ..., None, :
    ]  # (B,T,1,rope)
    k_rope = apply_rope(k_rope, positions, rope_base)[..., 0, :]
    k_nope = dense_apply(p["kv_b_k_proj"], c_kv, compute_dtype=compute_dtype)  # (B,T,H,nope)
    v = dense_apply(p["kv_b_v_proj"], c_kv, compute_dtype=compute_dtype)  # (B,T,H,v)

    # fold rope-part into a (H, nope+rope) layout: concat k_rope per head
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, cfg.qk_rope_dim))], axis=-1
    )
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    q_full = q_full.reshape(B, T, H, 1, q_full.shape[-1])  # K==H, G=1
    out = attend(q_full, k_full, v, positions, positions, causal=causal, window=window,
                 prefix_len=prefix_len, scale=_mla_scale(cfg), cap=0.0, q_chunk=q_chunk)
    out = out.reshape(B, T, H, cfg.v_head_dim)
    return dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)


def mla_init_cache(batch: int, max_len: int, cfg: MLAConfig, dtype=jnp.bfloat16):
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def mla_decode(p, x, cache, pos, *, cfg: MLAConfig, rope_base=10000.0,
               compute_dtype=jnp.bfloat16,
               block_tables: Optional[jax.Array] = None):
    """Absorbed decode: attention runs in the compressed kv_lora space.

    q_eff = q_nope @ kv_b_k   (per-head, rank-space query)
    logits = q_eff·c_kv + q_rope·k_rope ;  out = (probs·c_kv) @ kv_b_v
    Per-step FLOPs O(H·r·S) instead of O(H·(n+v)·r·S) re-expansion.
    ``block_tables``: paged c_kv/k_rope pools, same contract as attn_decode.
    """
    B, T, D = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    positions, per_row = decode_positions(pos, B)

    cq = rmsnorm_apply(p["q_a_norm"], dense_apply(p["q_a_proj"], x, compute_dtype=compute_dtype))
    q = dense_apply(p["q_b_proj"], cq, compute_dtype=compute_dtype)
    q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, rope_base)
    # absorb kv_b_k:  (B,1,H,n) x (r,H,n) -> (B,1,H,r).  as_dense: Packed
    # serving weights dequantize on the fly for the absorbed contraction.
    q_eff = jnp.einsum(
        "BTHn,rHn->BTHr", q_nope, as_dense(p["kv_b_k_proj"]["kernel"], compute_dtype)
    )

    c_new = rmsnorm_apply(
        p["kv_a_norm"], dense_apply(p["kv_a_proj"], x, compute_dtype=compute_dtype)
    )
    kr_new = dense_apply(p["k_rope_proj"], x, compute_dtype=compute_dtype)[..., None, :]
    kr_new = apply_rope(kr_new, positions, rope_base)[..., 0, :]
    if block_tables is not None:
        if not per_row:
            raise ValueError("paged decode requires per-row (B,) positions")
        idx = paged_token_index(block_tables, positions[:, 0], cache["c_kv"].shape[1])
        cache = _paged_write(cache, ("c_kv", "k_rope"), (c_new[:, 0], kr_new[:, 0]), idx)
        backend = resolve_attention_backend()
        if backend != "composed":
            out_c = _fused_paged_mla(
                q_eff, q_rope, cache, block_tables, positions,
                cfg=cfg, backend=backend, compute_dtype=compute_dtype,
            )
            out = jnp.einsum(
                "BTHr,rHv->BTHv", out_c, as_dense(p["kv_b_v_proj"]["kernel"], compute_dtype)
            )
            y = dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)
            return y, cache
        c_kv = _paged_read(cache, "c_kv", block_tables, compute_dtype, r)
        k_rope = _paged_read(cache, "k_rope", block_tables, compute_dtype, cfg.qk_rope_dim)
    else:
        cache = {
            "c_kv": cache_update_rows(cache["c_kv"], c_new, pos, per_row=per_row),
            "k_rope": cache_update_rows(cache["k_rope"], kr_new, pos, per_row=per_row),
        }
        c_kv = cache_read(cache["c_kv"], compute_dtype)
        k_rope = cache_read(cache["k_rope"], compute_dtype)
    S = c_kv.shape[1]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    mask = (kv_pos[None, :] <= positions)[:, None, None, :]  # (B,1,1,S)

    logits = (
        jnp.einsum("BTHr,BSr->BHTS", q_eff, c_kv)
        + jnp.einsum("BTHr,BSr->BHTS", q_rope, k_rope)
    ).astype(jnp.float32) * _mla_scale(cfg)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(compute_dtype)
    out_c = jnp.einsum("BHTS,BSr->BTHr", probs, c_kv)  # compressed values
    out = jnp.einsum("BTHr,rHv->BTHv", out_c, as_dense(p["kv_b_v_proj"]["kernel"], compute_dtype))
    y = dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)
    return y, cache


def mla_verify_paged(
    p,
    x,
    cache,
    block_tables,
    positions,
    *,
    cfg: MLAConfig,
    valid,
    rope_base=10000.0,
    compute_dtype=jnp.bfloat16,
):
    """Speculative multi-token MLA verify against the paged c_kv/k_rope
    pools (DESIGN.md §8).  The absorbed-decode einsums already carry a T
    axis, so this is ``mla_decode``'s paged branch with T > 1: scatter the
    T compressed entries per row at their global positions, gather, and
    mask each query to its own causal horizon.  x (B, T, D); positions /
    ``valid`` (B, T) as in ``attn_verify_paged``."""
    B, T, D = x.shape
    cq = rmsnorm_apply(p["q_a_norm"], dense_apply(p["q_a_proj"], x, compute_dtype=compute_dtype))
    q = dense_apply(p["q_b_proj"], cq, compute_dtype=compute_dtype)
    q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim :]
    q_rope = apply_rope(q_rope, positions, rope_base)
    q_eff = jnp.einsum(
        "BTHn,rHn->BTHr", q_nope, as_dense(p["kv_b_k_proj"]["kernel"], compute_dtype)
    )

    c_new = rmsnorm_apply(
        p["kv_a_norm"], dense_apply(p["kv_a_proj"], x, compute_dtype=compute_dtype)
    )
    kr_new = dense_apply(p["k_rope_proj"], x, compute_dtype=compute_dtype)[..., None, :]
    kr_new = apply_rope(kr_new, positions, rope_base)[..., 0, :]
    idx = verify_token_index(block_tables, positions, cache["c_kv"].shape[1], valid)
    cache = _verify_scatter(cache, ("c_kv", "k_rope"), (c_new, kr_new), idx)
    backend = resolve_attention_backend()
    if backend != "composed":
        out_c = _fused_paged_mla(
            q_eff, q_rope, cache, block_tables, positions,
            cfg=cfg, backend=backend, compute_dtype=compute_dtype,
        )
        out = jnp.einsum(
            "BTHr,rHv->BTHv", out_c, as_dense(p["kv_b_v_proj"]["kernel"], compute_dtype)
        )
        y = dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)
        return y, cache
    c_kv = _paged_read(cache, "c_kv", block_tables, compute_dtype, cfg.kv_lora_rank)
    k_rope = _paged_read(cache, "k_rope", block_tables, compute_dtype, cfg.qk_rope_dim)
    S = c_kv.shape[1]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    mask = (kv_pos[None, None, None, :] <= positions[:, None, :, None])  # (B,1,T,S)

    logits = (
        jnp.einsum("BTHr,BSr->BHTS", q_eff, c_kv)
        + jnp.einsum("BTHr,BSr->BHTS", q_rope, k_rope)
    ).astype(jnp.float32) * _mla_scale(cfg)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(compute_dtype)
    out_c = jnp.einsum("BHTS,BSr->BTHr", probs, c_kv)
    out = jnp.einsum("BTHr,rHv->BTHv", out_c, as_dense(p["kv_b_v_proj"]["kernel"], compute_dtype))
    y = dense_apply(p["o_proj"], out, n_in=2, compute_dtype=compute_dtype)
    return y, cache
