"""Pallas kernel: paged decode attention with the block-table gather fused
into the online-softmax loop (DESIGN.md §9).

The composed path (models/attention.py) resolves a row's cache through its
block table by materializing the (B, max_blocks·block, ...) logical view
every step — O(B·S·K·hd) HBM round-trips for a single-token query.  Here
the table lookup moves into the kernel's BlockSpec index_map: grid step
(b, kh, j) DMAs physical block ``block_tables[b, j]`` straight into VMEM,
computes that block's QK^T / softmax / PV contribution, and folds it into
the running (max, denominator, accumulator) — FlashAttention's recurrence
over the POOL's blocks, so the logical view never exists anywhere.

Grid and blocks (GQA kernel), kf = heads_per_step(K, T·G) KV heads a step:

  grid = (B, K/kf, max_blocks)       j innermost: scratch carries across j
  q    (B, K, T·G, hd)   block (-, kf, T·G, hd)  index (b, kg, 0, 0)
  k/v  (n_blocks, K, block, hd) block (-, kf, block, hd)
                                     index (block_tables[b, j], kg, 0, 0)
  out  (B, K, T·G, hd)   block (-, kf, T·G, hd)  written on the last j

The pools are head-major inside a physical block, so a step's kf heads of
one block are one contiguous (kf, block, hd) slab — one DMA per pool per
step — and every block's two minor dims are whole (block, hd) tiles, the
(8, 128) rule Mosaic holds every block to (``-`` marks a squeezed unit
dim).  A grid step costs a fixed ~0.45 µs on a TPU v5e whatever it carries,
so the kernel folds as many heads into a step as keeps kf·T·G within
ROWS_PER_STEP: all K at decode, fewer at the larger tail-prefill buckets,
one (a step per head) where T·G alone reaches the limit.  The step's heads
are the batch axis of its dots, so each head runs the same per-head
recurrence; the call's name carries the fold (``paged_attention_int8_h8``),
so a trace splits the kernel's time by it.

``block_tables`` (and the per-row first query position + window) ride as
scalar-prefetch operands (PrefetchScalarGridSpec), so the index_map reads
them before the grid runs — the canonical Pallas paged-attention pattern.

Query rows must be CONTIGUOUS: row r of the folded T·G axis is query
token t = r//G at global position pos0[b] + t.  Every caller satisfies
this (decode T=1, the verify pass positions[b, t] = pos[b] + t, and the
tail-prefill bucket start + arange(T)).  In-kernel masking reproduces the
composed path exactly: kv_pos <= q_pos (causal), q_pos - kv_pos < window
(sliding window; pass 2^30 for global layers — the config sentinel), with
masked logits at -1e30 before the max and exp'd terms zeroed so a fully
masked block contributes nothing.  int8 fixed-point pools dequantize in
the kernel (× 2^-KV_F, an exponent shift) — ``kv_scale`` is static on the
pool dtype.  Per-block SYMOG pools (DESIGN.md §11) instead carry int32
exponent leaves: the ``_quant`` kernel variants read the physical block's
(1, K) exponent row through the SAME prefetched table as the data block
and pick each of the step's heads with a masked lane sum, unpack packed
int4 words with a lane concatenate (core.packing split planes), and
dequantize with one exp2 multiply inside the loop.

The online recurrence per block j (m running max, l denominator, o acc):

  s      = scale · q k_j^T            (softcap'd, then masked to -1e30)
  m'     = max(m, rowmax(s))
  alpha  = exp(m - m')
  p      = where(mask, exp(s - m'), 0)
  l'     = alpha·l + rowsum(p)
  o'     = alpha·o + p v_j
  out    = o / l                       (after the last block)

The MLA kernel is the same recurrence with two pool operands — logits are
q_eff·c_kv + q_rope·k_rope over the compressed (rank r) and rope pools,
and the value IS c_kv (absorbed decode) — on grid (B, max_blocks) with all
H heads folded into the query-row axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack_planes

NEG_INF = -1e30  # matches the composed path's masked-logit fill
# T·G query rows of the largest tail-prefill call that runs on the chip
# (a 2048-token bucket x G=2): a folded step holds no more rows than it
ROWS_PER_STEP = 4096


def heads_per_step(K: int, tg: int) -> int:
    """KV heads one GQA grid step carries: the largest divisor kf of the
    (shard-local) K with kf·tg <= ROWS_PER_STEP, and 1 where tg alone
    reaches it."""
    fits = [d for d in range(1, K + 1) if K % d == 0 and d * tg <= ROWS_PER_STEP]
    return max(fits, default=1)


def _dot(a, b, contract):
    """f32-accumulated dot over ``contract`` ((lhs dim,), (rhs dim,)),
    batched over a leading head axis where the operands carry one."""
    batch = ((0,), (0,)) if a.ndim == 3 else ((), ())
    return jax.lax.dot_general(a, b, (contract, batch), preferred_element_type=jnp.float32)


def _online_update(mask, s, v, m_ref, l_ref, acc_ref):
    """One block's fold into the running (max, denom, acc) scratch; with a
    leading head axis, each head's fold runs side by side."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + _dot(p, v, ((p.ndim - 1,), (v.ndim - 2,)))


def _init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finish(o_ref, l_ref, acc_ref):
    # l == 0 only for queries with no visible key (padded tail-prefill
    # rows whose output is garbage either way) — keep it finite.
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _qk(q, k):
    """q (rows, d) · k (block, d)^T without materializing the transpose
    (per head: q (h, rows, d), k (h, block, d))."""
    return _dot(q, k, ((q.ndim - 1,), (k.ndim - 1,)))


def _dequant(words, exp, kv_bits):
    """int8 mantissa words (int4: two per word) × 2^exp, exp a (1, 1) array
    or one per head, (h, 1, 1)."""
    m = unpack_planes(words, 4) if kv_bits == 4 else words.astype(jnp.int32)
    return m.astype(jnp.float32) * jnp.exp2(exp.astype(jnp.float32))


def _heads_exp(e_ref, kg, kf):
    """Exponents of heads kg·kf .. kg·kf + kf - 1 from the block's (1, K)
    exponent row, as (kf, 1, 1): a masked lane sum per head."""
    e = jnp.broadcast_to(e_ref[...][None], (kf, 1, e_ref.shape[-1]))
    lane = jax.lax.broadcasted_iota(jnp.int32, e.shape, 2)
    head = kg * kf + jax.lax.broadcasted_iota(jnp.int32, e.shape, 0)
    return jnp.sum(jnp.where(lane == head, e, 0), axis=2, keepdims=True)


def _attn_kernel(bt_ref, pos_ref, win_ref, q_ref, k_ref, v_ref, *rest,
                 block: int, nb: int, g: int, kf: int, scale: float,
                 cap: float, kv_scale: float, kv_bits: int):
    """GQA body over a step's kf KV heads, the head axis the batch axis of
    every dot: each head runs the per-head recurrence.  Float / KV_F int8
    pools scale by the static ``kv_scale``; per-block-scale pools
    (``kv_bits`` 8 or 4) carry two exponent operands ahead of the output
    and dequantize each head with its own exponent."""
    if kv_bits:
        ke_ref, ve_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, kg, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    tg = q_ref.shape[1]
    q = q_ref[...].astype(jnp.float32)
    if kv_bits:
        k = _dequant(k_ref[...], _heads_exp(ke_ref, kg, kf), kv_bits)
        v = _dequant(v_ref[...], _heads_exp(ve_ref, kg, kf), kv_bits)
    else:
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        if kv_scale != 1.0:  # int8 fixed-point pool: exponent-shift dequant
            k = k * kv_scale
            v = v * kv_scale

    s = _qk(q, k) * scale
    if cap > 0:
        s = jnp.tanh(s / cap) * cap

    q_pos = pos_ref[b] + jax.lax.broadcasted_iota(jnp.int32, (1, tg, 1), 1) // g
    kv_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block), 2)
    mask = (kv_pos <= q_pos) & (q_pos - kv_pos < win_ref[0])
    _online_update(mask, s, v, m_ref, l_ref, acc_ref)

    @pl.when(j == nb - 1)
    def _done():
        _finish(o_ref, l_ref, acc_ref)


def paged_attention_padded(q, k_pool, v_pool, block_tables, pos0, window, *,
                           g: int, scale: float, cap: float, kv_scale: float,
                           k_exp=None, v_exp=None, kv_bits: int = 0,
                           interpret: bool = False):
    """q (B, K, T·G, hd) float; k/v pools (n_blocks, K, block, hd) float or
    int8; block_tables (B, max_blocks) int32; pos0 (B,) int32 first query
    position per row (queries contiguous); window (1,) int32 (2^30 =
    unwindowed).  Returns (B, K, T·G, hd) f32-accumulated in q's dtype.

    Per-block-scale pools pass ``k_exp``/``v_exp`` (n_blocks, K) int32
    exponents plus ``kv_bits`` (8, or 4 for packed pools whose last dim is
    hd/2); each grid step reads its physical block's exponent row through
    the same scalar-prefetched table as the data block.  A step carries
    ``heads_per_step(K, T·G)`` KV heads of one row and one table block."""
    B, K, tg, hd = q.shape
    block = k_pool.shape[2]
    nb = block_tables.shape[1]
    hdw = k_pool.shape[3]  # hd, or hd//2 for packed int4 words
    quant = k_exp is not None
    kf = heads_per_step(K, tg)
    kv_spec = pl.BlockSpec(
        (None, kf, block, hdw), lambda b, kg, j, bt, pos, win: (bt[b, j], kg, 0, 0)
    )
    q_spec = pl.BlockSpec((None, kf, tg, hd), lambda b, kg, j, bt, pos, win: (b, kg, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k_pool, v_pool]
    if quant:
        e_spec = pl.BlockSpec((None, 1, K), lambda b, kg, j, bt, pos, win: (bt[b, j], 0, 0))
        in_specs += [e_spec, e_spec]
        operands += [k_exp.reshape(-1, 1, K), v_exp.reshape(-1, 1, K)]
    body = functools.partial(
        _attn_kernel, block=block, nb=nb, g=g, kf=kf, scale=scale, cap=cap,
        kv_scale=kv_scale, kv_bits=kv_bits if quant else 0,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, K // kf, nb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kf, tg, 1), jnp.float32),
            pltpu.VMEM((kf, tg, 1), jnp.float32),
            pltpu.VMEM((kf, tg, hd), jnp.float32),
        ],
    )
    base = f"paged_attention_int{kv_bits}" if quant else "paged_attention"
    name = f"{base}_h{kf}"
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, tg, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(block_tables, pos0, window, *operands)


def _mla_kernel(bt_ref, pos_ref, qe_ref, qr_ref, ckv_ref, kr_ref, *rest,
                block: int, nb: int, h: int, scale: float, kv_scale: float,
                kv_bits: int):
    """Absorbed-MLA body; per-block-scale pools (``kv_bits`` 8 or 4) carry
    one scalar exponent per physical block and stream."""
    if kv_bits:
        ce_ref, re_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    th = qe_ref.shape[0]
    qe = qe_ref[...].astype(jnp.float32)
    qr = qr_ref[...].astype(jnp.float32)
    if kv_bits:
        ckv = _dequant(ckv_ref[...], ce_ref[...], kv_bits)
        kr = _dequant(kr_ref[...], re_ref[...], kv_bits)
    else:
        ckv = ckv_ref[...].astype(jnp.float32)
        kr = kr_ref[...].astype(jnp.float32)
        if kv_scale != 1.0:
            ckv = ckv * kv_scale
            kr = kr * kv_scale

    s = (_qk(qe, ckv) + _qk(qr, kr)) * scale

    q_pos = pos_ref[b] + jax.lax.broadcasted_iota(jnp.int32, (th, 1), 0) // h
    kv_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    mask = kv_pos <= q_pos
    _online_update(mask, s, ckv, m_ref, l_ref, acc_ref)

    @pl.when(j == nb - 1)
    def _done():
        _finish(o_ref, l_ref, acc_ref)


def paged_attention_mla_padded(q_eff, q_rope, ckv_pool, krope_pool,
                               block_tables, pos0, *, h: int, scale: float,
                               kv_scale: float, ckv_exp=None, kr_exp=None,
                               kv_bits: int = 0, interpret: bool = False):
    """q_eff (B, T·H, r), q_rope (B, T·H, rope); pools (n_blocks, block, r)
    and (n_blocks, block, rope).  Absorbed MLA decode: the value stream is
    the compressed c_kv itself, so out is (B, T·H, r).  Per-block-scale
    pools pass ``ckv_exp``/``kr_exp`` (n_blocks,) int32 exponents plus
    ``kv_bits`` (8, or 4 for packed pools whose last dim is halved)."""
    B, th, r = q_eff.shape
    rope = q_rope.shape[2]
    block = ckv_pool.shape[1]
    nb = block_tables.shape[1]
    quant = ckv_exp is not None
    rw, ropew = ckv_pool.shape[2], krope_pool.shape[2]  # halved when packed
    pool_spec = lambda w: pl.BlockSpec(  # noqa: E731
        (None, block, w), lambda b, j, bt, pos: (bt[b, j], 0, 0))
    in_specs = [
        pl.BlockSpec((None, th, r), lambda b, j, bt, pos: (b, 0, 0)),
        pl.BlockSpec((None, th, rope), lambda b, j, bt, pos: (b, 0, 0)),
        pool_spec(rw),
        pool_spec(ropew),
    ]
    operands = [q_eff, q_rope, ckv_pool, krope_pool]
    if quant:
        e_spec = pl.BlockSpec((None, 1, 1), lambda b, j, bt, pos: (bt[b, j], 0, 0))
        in_specs += [e_spec, e_spec]
        operands += [ckv_exp.reshape(-1, 1, 1), kr_exp.reshape(-1, 1, 1)]
    body = functools.partial(
        _mla_kernel, block=block, nb=nb, h=h, scale=scale, kv_scale=kv_scale,
        kv_bits=kv_bits if quant else 0,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, th, r), lambda b, j, bt, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((th, 1), jnp.float32),
            pltpu.VMEM((th, 1), jnp.float32),
            pltpu.VMEM((th, r), jnp.float32),
        ],
    )
    name = f"paged_attention_mla_int{kv_bits}" if quant else "paged_attention_mla"
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, th, r), q_eff.dtype),
        interpret=interpret,
        name=name,
    )(block_tables, pos0, *operands)
