"""Pure-jnp oracle: the composed gather → mask → softmax paged attention.

This mirrors models/attention.py's reference path (``gather_logical`` + the
dense masked softmax) without importing it — kernels sit below models in
the layering.  Parity tests assert the fused kernel against BOTH this
oracle and the real composed layer code."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packing import unpack_int


def gather_logical(pool, block_tables):
    """REFERENCE paged cache view (DESIGN.md §9): each row's token-major
    logical cache (B, max_blocks·block, ...) — what the kernel avoids.  KV
    pools are head-major inside a block, (n_blocks, K, block, hd); MLA
    rank-space pools are (n_blocks, block, r).  Entries whose table slot is
    trash (or beyond the row's position) are garbage — callers mask them
    with kv_pos <= pos, exactly like the dense tail."""
    g = pool[block_tables]  # (B, max_blocks, [K,] block, w)
    if pool.ndim == 4:
        g = g.swapaxes(2, 3)
    return g.reshape(g.shape[:1] + (-1,) + g.shape[3:])


def dequant_logical(pool, exp_leaf, block_tables, *, kv_bits):
    """Gathered logical view of a SYMOG-quantized pool: int4 words unpacked,
    then every row of physical block p scaled by 2^exp_leaf[p] (per KV head
    where the exponent leaf carries a head axis)."""
    data = gather_logical(pool, block_tables)
    if kv_bits == 4:  # core.packing split planes, two lanes per word
        data = unpack_int(data, 4, 2 * data.shape[-1])
    block = pool.shape[2] if pool.ndim == 4 else pool.shape[1]
    e = jnp.repeat(exp_leaf[block_tables], block, axis=1)  # (B, S[, K])
    scale = jnp.exp2(e.astype(jnp.float32))
    scale = scale[:, :, None] if e.ndim == 2 else scale[:, :, :, None]
    return data.astype(jnp.float32) * scale


def paged_attention_ref(q, k_pool, v_pool, block_tables, pos0, *, scale,
                        cap=0.0, window=None, kv_scale=1.0,
                        k_scale_exp=None, v_scale_exp=None, kv_bits=0):
    """Composed reference for ``paged_attention`` (same contract)."""
    B, T, K, G, hd = q.shape
    if k_scale_exp is not None:
        k = dequant_logical(k_pool, k_scale_exp, block_tables, kv_bits=kv_bits)
        v = dequant_logical(v_pool, v_scale_exp, block_tables, kv_bits=kv_bits)
    else:
        k = gather_logical(k_pool, block_tables).astype(jnp.float32) * kv_scale
        v = gather_logical(v_pool, block_tables).astype(jnp.float32) * kv_scale
    S = k.shape[1]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    q_pos = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    mask = kv_pos[None, None, :] <= q_pos[:, :, None]  # (B, T, S)
    if window is not None:
        mask = mask & (q_pos[:, :, None] - kv_pos[None, None, :] < window)
    logits = jnp.einsum(
        "BTKGh,BSKh->BKGTS", q.astype(jnp.float32), k
    ) * scale
    if cap > 0:
        logits = jnp.tanh(logits / cap) * cap
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("BKGTS,BSKh->BTKGh", probs, v).astype(q.dtype)


def paged_attention_mla_ref(q_eff, q_rope, ckv_pool, krope_pool,
                            block_tables, pos0, *, scale, kv_scale=1.0,
                            ckv_scale_exp=None, kr_scale_exp=None, kv_bits=0):
    """Composed reference for ``paged_attention_mla`` (same contract)."""
    B, T, H, r = q_eff.shape
    if ckv_scale_exp is not None:
        c_kv = dequant_logical(ckv_pool, ckv_scale_exp, block_tables, kv_bits=kv_bits)
        k_rope = dequant_logical(krope_pool, kr_scale_exp, block_tables, kv_bits=kv_bits)
    else:
        c_kv = gather_logical(ckv_pool, block_tables).astype(jnp.float32) * kv_scale
        k_rope = gather_logical(krope_pool, block_tables).astype(jnp.float32) * kv_scale
    S = c_kv.shape[1]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    q_pos = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    mask = kv_pos[None, None, None, :] <= q_pos[:, None, :, None]  # (B,1,T,S)
    logits = (
        jnp.einsum("BTHr,BSr->BHTS", q_eff.astype(jnp.float32), c_kv)
        + jnp.einsum("BTHr,BSr->BHTS", q_rope.astype(jnp.float32), k_rope)
    ) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("BHTS,BSr->BTHr", probs, c_kv).astype(q_eff.dtype)
