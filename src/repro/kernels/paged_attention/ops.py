"""Public wrappers: fused paged attention for decode / verify / tail-prefill.

Callers hand the kernel the SAME operands the composed path consumes — the
(B, T, K, G, hd) query block, the (n_blocks, K, block, hd) pools (MLA:
(n_blocks, block, r)) and the
(B, max_blocks) tables — plus the per-row FIRST query position; queries
must be contiguous (q_pos[b, t] = pos0[b] + t), which every serving call
site satisfies (decode T=1, speculative verify, bucketed tail prefill).

``window=None`` means unwindowed and maps onto the config's 2^30 sentinel
(GLOBAL_WINDOW), so one trace serves static-None callers and the traced
per-layer window scalar the gemma2/3 scan bodies carry.  ``kv_scale`` is
the pool dequantization scale: 1.0 for float pools, 2^-KV_F for the int8
fixed-point cache (static on the pool dtype — the caller passes it).

Under an ambient mesh with a ``model`` axis (``jax.set_mesh``, DESIGN.md
§12) the public wrappers shard-map over KV heads when they divide: each
model shard runs the SAME kernel on its local (B, T, K/m, G, hd) query
slice against its local (n_blocks, K/m, block, hd) pool slice — attention
is embarrassingly parallel across KV-head groups, so no collective
appears; the o-projection's contraction psum is GSPMD's job outside this
op.  MLA shards the H query heads instead and reads the (replicated)
rank-space pools whole.  Heads that don't divide run the whole call on
every device (``dispatch.shard_kernel``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention.kernel import (
    paged_attention_padded,
    paged_attention_mla_padded,
)
from repro.kernels.dispatch import shard_kernel
from repro.nn.sharding import current_mesh, mesh_axis_size

_NO_WINDOW = 2**30  # models.config.GLOBAL_WINDOW (no models import: layering)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "cap", "kv_scale", "kv_bits", "interpret", "out_dtype"),
)
def _paged_attention(q, k_pool, v_pool, block_tables, pos0, window, k_exp,
                     v_exp, *, scale, cap, kv_scale, kv_bits, interpret,
                     out_dtype):
    B, T, K, G, hd = q.shape
    q2 = q.transpose(0, 2, 1, 3, 4).reshape(B, K, T * G, hd)
    out = paged_attention_padded(
        q2, k_pool, v_pool,
        block_tables.astype(jnp.int32),
        pos0.astype(jnp.int32),
        window,
        g=G, scale=scale, cap=cap, kv_scale=kv_scale,
        k_exp=k_exp, v_exp=v_exp, kv_bits=kv_bits, interpret=interpret,
    )
    out = out.reshape(B, K, T, G, hd).transpose(0, 2, 1, 3, 4)
    return out.astype(out_dtype) if out_dtype is not None else out


def paged_attention(q, k_pool, v_pool, block_tables, pos0, *, scale: float,
                    cap: float = 0.0, window=None, kv_scale: float = 1.0,
                    k_scale_exp=None, v_scale_exp=None, kv_bits: int = 0,
                    interpret: bool = False, out_dtype=None):
    """Fused paged GQA/MQA attention.

    q (B, T, K, G, hd); k/v pools (n_blocks, K, block, hd) float or int8;
    block_tables (B, max_blocks) int32 (trash block 0 for unused slots);
    pos0 (B,) int32.  ``window`` None, a Python int, or a traced int32
    scalar; ``cap`` the logit softcap (0 = off).  Masking, windowing and
    int8 dequantization all happen inside the online-softmax loop — the
    (B, max_blocks·block, ...) logical view is never materialized.

    Per-block SYMOG pools pass ``k_scale_exp``/``v_scale_exp`` (n_blocks,
    K) int32 exponent leaves and ``kv_bits`` in {8, 4}; int4 pools pack two
    lanes per int8 word, so their last dim is hd/2 and the kernel unpacks
    in-lane (``kv_scale`` is ignored on this path)."""
    w = _NO_WINDOW if window is None else window
    w = jnp.asarray(w, jnp.int32).reshape(1)
    call = functools.partial(
        _paged_attention,
        scale=scale, cap=cap, kv_scale=kv_scale, kv_bits=kv_bits,
        interpret=interpret, out_dtype=out_dtype,
    )
    args = (q, k_pool, v_pool, block_tables, pos0, w, k_scale_exp, v_scale_exp)
    m = mesh_axis_size(current_mesh(), "model")
    if m > 1 and q.shape[2] % m == 0:
        # §12 head slicing: pools and queries split on the KV-head axis,
        # tables/positions/window replicated — each shard's kernel sees a
        # (B, T, K/m, G, hd) problem against its local pool slice
        heads, pool = P(None, None, "model"), P(None, "model")
        in_specs = (
            heads, pool, pool, P(), P(), P(),
            pool if k_scale_exp is not None else P(),
            pool if v_scale_exp is not None else P(),
        )
        return shard_kernel(call, *args, in_specs=in_specs, out_specs=heads)
    return shard_kernel(call, *args)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "kv_scale", "kv_bits", "interpret", "out_dtype"),
)
def _paged_attention_mla(q_eff, q_rope, ckv_pool, krope_pool, block_tables,
                         pos0, ckv_exp, kr_exp, *, scale, kv_scale, kv_bits,
                         interpret, out_dtype):
    B, T, H, r = q_eff.shape
    rope = q_rope.shape[-1]
    out = paged_attention_mla_padded(
        q_eff.reshape(B, T * H, r),
        q_rope.reshape(B, T * H, rope),
        ckv_pool, krope_pool,
        block_tables.astype(jnp.int32),
        pos0.astype(jnp.int32),
        h=H, scale=scale, kv_scale=kv_scale,
        ckv_exp=ckv_exp, kr_exp=kr_exp, kv_bits=kv_bits, interpret=interpret,
    )
    out = out.reshape(B, T, H, r)
    return out.astype(out_dtype) if out_dtype is not None else out


def paged_attention_mla(q_eff, q_rope, ckv_pool, krope_pool, block_tables,
                        pos0, *, scale: float, kv_scale: float = 1.0,
                        ckv_scale_exp=None, kr_scale_exp=None,
                        kv_bits: int = 0, interpret: bool = False,
                        out_dtype=None):
    """Fused paged MLA absorbed decode (DESIGN.md §9).

    q_eff (B, T, H, r) rank-space queries; q_rope (B, T, H, rope); pools
    (n_blocks, block, r) / (n_blocks, block, rope).  Logits are
    q_eff·c_kv + q_rope·k_rope and the VALUE stream is c_kv itself, so the
    result (B, T, H, r) still needs the caller's kv_b_v expansion.
    Per-block SYMOG pools pass ``ckv_scale_exp``/``kr_scale_exp``
    (n_blocks,) int32 exponents and ``kv_bits`` in {8, 4}."""
    call = functools.partial(
        _paged_attention_mla,
        scale=scale, kv_scale=kv_scale, kv_bits=kv_bits, interpret=interpret,
        out_dtype=out_dtype,
    )
    args = (q_eff, q_rope, ckv_pool, krope_pool, block_tables, pos0, ckv_scale_exp,
            kr_scale_exp)
    m = mesh_axis_size(current_mesh(), "model")
    if m > 1 and q_eff.shape[2] % m == 0:
        # MLA has no KV-head axis — shard the H QUERY heads and read the
        # (replicated) rank-space pools whole on every shard (§12: their
        # bytes are already compressed by the low-rank factor)
        heads = P(None, None, "model")
        in_specs = (heads, heads, P(), P(), P(), P(), P(), P())
        return shard_kernel(call, *args, in_specs=in_specs, out_specs=heads)
    return shard_kernel(call, *args)
