"""Per-layer blocks with a uniform (init / apply / decode / cache) interface.

Kinds:
  'A' — pre-norm attention + pre-norm MLP (gemma2/3 add post-norms)
  'D' — same but used for MoE models' leading dense layers (MLA attention
        when cfg.use_mla)
  'E' — attention + MoE FFN
  'R' — RG-LRU recurrent block + MLP (recurrentgemma)
  'M' — mamba2 SSD block (no separate MLP)

``window``/``rope_base`` may be traced scalars (scanned per-layer) — local
vs global attention is data, not structure, so gemma2/3 stay one lax.scan.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models.attention import (
    AttnConfig,
    MLAConfig,
    attn_apply,
    attn_decode,
    attn_init,
    attn_init_cache,
    attn_prefill_paged,
    attn_verify_paged,
    mla_apply,
    mla_decode,
    mla_init,
    mla_init_cache,
    mla_verify_paged,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_rope,
    dense_apply,
    layernorm_apply,
    layernorm_init,
    rmsnorm_apply,
    rmsnorm_init,
)
from repro.models.mlp import MLPConfig, mlp_apply, mlp_init
from repro.models.moe import MoEConfig, moe_apply, moe_init
from repro.nn.sharding import current_mesh, mesh_axis_size
from repro.models.rglru import (
    RGLRUConfig,
    rglru_block_apply,
    rglru_block_decode,
    rglru_init,
    rglru_init_cache,
)
from repro.models.ssd import (
    SSDConfig,
    ssd_block_apply,
    ssd_block_decode,
    ssd_init,
    ssd_init_cache,
)


def _attn_cfg(cfg: ModelConfig) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        rope=cfg.use_rope,
        qk_norm=cfg.qk_norm,
        softcap=cfg.attn_softcap,
        bias=cfg.attn_bias,
        query_scale=cfg.query_scale,
    )


def _mla_cfg(cfg: ModelConfig) -> MLAConfig:
    return MLAConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim,
    )


def _mlp_cfg(cfg: ModelConfig) -> MLPConfig:
    return MLPConfig(
        d_model=cfg.d_model, d_ff=cfg.d_ff, gated=cfg.mlp_gated, act=cfg.act, bias=cfg.attn_bias
    )


def _ep_active(cfg: ModelConfig) -> bool:
    """True when the ambient mesh (``jax.set_mesh`` — readable mid-trace)
    carries the config's EP axes at total size > 1 and the expert count
    divides over them: the condition under which ``moe_impl='ep'`` actually
    dispatches the shard_map expert-parallel path (DESIGN.md §12).
    Single-device tracing falls back to the scatter/gather dispatch — the
    same routing decisions, so the fallback is token-compatible."""
    mesh = current_mesh()
    if mesh is None:
        return False
    ep = mesh_axis_size(mesh, *cfg.ep_axes)
    return ep > 1 and cfg.n_experts % ep == 0


def _moe_cfg(cfg: ModelConfig) -> MoEConfig:
    return MoEConfig(
        d_model=cfg.d_model,
        n_experts=cfg.n_experts,
        top_k=cfg.top_k,
        d_ff_expert=cfg.d_ff_expert,
        n_shared_experts=cfg.n_shared_experts,
        router=cfg.router,
        capacity_factor=cfg.capacity_factor,
        act=cfg.act,
        ep_axes=tuple(cfg.ep_axes),
    )


def _rglru_cfg(cfg: ModelConfig) -> RGLRUConfig:
    return RGLRUConfig(
        d_model=cfg.d_model, d_rnn=cfg.d_rnn, n_heads=cfg.rnn_heads, conv_width=cfg.conv_width
    )


def _ssd_cfg(cfg: ModelConfig) -> SSDConfig:
    return SSDConfig(
        d_model=cfg.d_model,
        d_inner=cfg.d_inner,
        n_heads=cfg.ssm_heads,
        head_dim=cfg.ssm_head_dim,
        d_state=cfg.ssm_state,
        conv_width=cfg.conv_width,
        chunk=cfg.ssd_chunk,
    )


def _norm_init(cfg: ModelConfig, dtype):
    if cfg.norm == "rmsnorm":
        return rmsnorm_init(cfg.d_model, dtype)
    return layernorm_init(cfg.d_model, dtype)


def _norm_apply(cfg: ModelConfig, p, x):
    return rmsnorm_apply(p, x) if cfg.norm == "rmsnorm" else layernorm_apply(p, x)


def zero_aux() -> Dict[str, jax.Array]:
    return {"moe_aux_loss": jnp.zeros(()), "moe_z_loss": jnp.zeros(())}


def _tag(x, name: str):
    """checkpoint_name tag — lets remat_policy='block_outputs' save exactly
    the all-reduced sublayer outputs (repro.models.lm builds the policy)."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(x, name)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def block_init(key, cfg: ModelConfig, kind: str, dtype=jnp.float32, cross: bool = False):
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    if kind == "M":
        p["pre_norm"] = _norm_init(cfg, dtype)
        p["ssd"] = ssd_init(ks[0], _ssd_cfg(cfg), dtype)
        return p
    if kind == "R":
        p["pre_norm"] = _norm_init(cfg, dtype)
        p["rglru"] = rglru_init(ks[0], _rglru_cfg(cfg), dtype)
    else:
        p["pre_norm"] = _norm_init(cfg, dtype)
        if cfg.use_mla:
            p["attn"] = mla_init(ks[0], _mla_cfg(cfg), dtype)
        else:
            p["attn"] = attn_init(ks[0], _attn_cfg(cfg), dtype)
        if cfg.post_norm:
            p["post_attn_norm"] = _norm_init(cfg, dtype)
        if cross:
            p["cross_norm"] = _norm_init(cfg, dtype)
            p["cross_attn"] = attn_init(ks[2], _attn_cfg(cfg), dtype)
    p["pre_mlp_norm"] = _norm_init(cfg, dtype)
    if kind == "E":
        p["moe"] = moe_init(ks[1], _moe_cfg(cfg), dtype)
    else:
        p["mlp"] = mlp_init(ks[1], _mlp_cfg(cfg), dtype)
    if cfg.post_norm:
        p["post_mlp_norm"] = _norm_init(cfg, dtype)
    return p


# ---------------------------------------------------------------------------
# full-sequence apply
# ---------------------------------------------------------------------------
def block_apply(
    p,
    x,
    *,
    cfg: ModelConfig,
    kind: str,
    positions,
    window=None,
    rope_base=10000.0,
    prefix_len: int = 0,
    causal: bool = True,
    compute_dtype=jnp.bfloat16,
    enc_out: Optional[jax.Array] = None,
    cache_len: int = 0,
    seq_len=None,
) -> Tuple[jax.Array, Dict, Any]:
    """Returns (x, aux, cache).  ``cache_len``>0 pads/records the layer cache
    (prefill); otherwise cache is None-shaped zeros to keep scan uniform.

    ``seq_len`` (traced scalar): bucketed-prefill valid length — positions
    >= seq_len are padding.  Causal attention already isolates real
    positions from a right-padded tail, so only the couplings that are not
    per-token causal consume it: MoE capacity dispatch, and the recurrent /
    SSD state+conv caches."""
    aux = zero_aux()
    cache = None
    B, T, _ = x.shape

    if kind == "M":
        h = _norm_apply(cfg, p["pre_norm"], x)
        y, cache = ssd_block_apply(p["ssd"], h, cfg=_ssd_cfg(cfg), compute_dtype=compute_dtype,
                                   seq_len=seq_len)
        return x + _tag(y, "block_out"), aux, cache

    if kind == "R":
        h = _norm_apply(cfg, p["pre_norm"], x)
        y, cache = rglru_block_apply(p["rglru"], h, cfg=_rglru_cfg(cfg),
                                     compute_dtype=compute_dtype, seq_len=seq_len)
        x = x + _tag(y, "block_out")
    else:
        h = _norm_apply(cfg, p["pre_norm"], x)
        if cfg.use_mla:
            y = mla_apply(p["attn"], h, cfg=_mla_cfg(cfg), positions=positions, causal=causal,
                          window=window, prefix_len=prefix_len,
                          rope_base=rope_base, compute_dtype=compute_dtype)
            if cache_len:
                cache = _mla_prefill_cache(
                    p["attn"], h, cfg, cache_len, positions, rope_base, compute_dtype
                )
        else:
            y = attn_apply(p["attn"], h, cfg=_attn_cfg(cfg), positions=positions, causal=causal,
                           window=window, prefix_len=prefix_len,
                           rope_base=rope_base, compute_dtype=compute_dtype)
            if cache_len:
                cache = _attn_prefill_cache(
                    p["attn"], h, cfg, cache_len, positions, rope_base, compute_dtype
                )
        # tag BEFORE the post-norm: the saved tensor must be the all-reduced
        # sublayer output itself, else the rematted backward re-runs the
        # collective to rebuild the norm input (measured in §Perf it.2).
        # The barrier also pins the wire dtype: without it XLA hoists the
        # norm's f32 upcast above the all-reduce (2× wire bytes).
        y = jax.lax.optimization_barrier(_tag(y, "block_out"))
        if cfg.post_norm:
            y = _norm_apply(cfg, p["post_attn_norm"], y)
        x = x + y
        if enc_out is not None:
            h = _norm_apply(cfg, p["cross_norm"], x)
            k_c = dense_apply(p["cross_attn"]["k_proj"], enc_out, compute_dtype=compute_dtype)
            v_c = dense_apply(p["cross_attn"]["v_proj"], enc_out, compute_dtype=compute_dtype)
            y = attn_apply(
                p["cross_attn"],
                h,
                cfg=_attn_cfg(cfg),
                positions=positions,
                causal=False,
                rope_base=rope_base,
                compute_dtype=compute_dtype,
                kv=(k_c, v_c),
            )
            x = x + _tag(y, "block_out")

    h = _norm_apply(cfg, p["pre_mlp_norm"], x)
    if kind == "E":
        if cfg.moe_impl == "ep" and _ep_active(cfg):
            from repro.models.moe_ep import moe_apply_ep

            # capacity_mult mirrors the dispatch path's capacity_factor so
            # the two routings drop (or don't) under the same pressure
            y, aux = moe_apply_ep(p["moe"], h, cfg=_moe_cfg(cfg), compute_dtype=compute_dtype,
                                  ep_axes=tuple(cfg.ep_axes), seq_len=seq_len,
                                  capacity_mult=cfg.capacity_factor)
        else:
            y, aux = moe_apply(p["moe"], h, cfg=_moe_cfg(cfg), compute_dtype=compute_dtype,
                               seq_len=seq_len)
    else:
        y = mlp_apply(p["mlp"], h, cfg=_mlp_cfg(cfg), compute_dtype=compute_dtype)
    y = jax.lax.optimization_barrier(_tag(y, "block_out"))
    if cfg.post_norm:
        y = _norm_apply(cfg, p["post_mlp_norm"], y)
    return x + y, aux, cache


def _attn_prefill_cache(
    pa, h, cfg: ModelConfig, cache_len: int, positions, rope_base, compute_dtype
):
    """Recompute roped k/v (cheap vs attention) and pad into the cache buffer."""
    k = dense_apply(pa["k_proj"], h, compute_dtype=compute_dtype)
    v = dense_apply(pa["v_proj"], h, compute_dtype=compute_dtype)
    if cfg.qk_norm:
        k = rmsnorm_apply(pa["k_norm"], k)
    if cfg.use_rope:
        k = apply_rope(k, positions, rope_base)
    B, T = h.shape[0], h.shape[1]
    pad = cache_len - T
    k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # float caches store at COMPUTE dtype (bf16 in production; f32 when the
    # engine computes f32), so cached k/v is bit-identical to the values
    # prefill attention consumed — the prefix-cache tail prefill (DESIGN.md
    # §7) attends cached prefix KV and must match the full-prefill oracle
    dt = jnp.int8 if cfg.kv_cache_dtype == "int8_fp" else jnp.dtype(compute_dtype)
    return {"k": attn_mod.cache_write(k, dt), "v": attn_mod.cache_write(v, dt)}


def _mla_prefill_cache(
    pa, h, cfg: ModelConfig, cache_len: int, positions, rope_base, compute_dtype
):
    c_kv = rmsnorm_apply(
        pa["kv_a_norm"], dense_apply(pa["kv_a_proj"], h, compute_dtype=compute_dtype)
    )
    k_rope = dense_apply(pa["k_rope_proj"], h, compute_dtype=compute_dtype)[..., None, :]
    k_rope = apply_rope(k_rope, positions, rope_base)[..., 0, :]
    pad = cache_len - h.shape[1]
    c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
    k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    dt = jnp.int8 if cfg.kv_cache_dtype == "int8_fp" else jnp.dtype(compute_dtype)
    return {"c_kv": attn_mod.cache_write(c_kv, dt), "k_rope": attn_mod.cache_write(k_rope, dt)}


def block_prefill_paged(
    p,
    x,
    cache,
    bt_row,
    positions,
    *,
    cfg: ModelConfig,
    window=None,
    rope_base=10000.0,
    seq_len=None,
    compute_dtype=jnp.bfloat16,
):
    """Prefix-cache tail prefill for an attention ('A') block (DESIGN.md §7).

    Same per-token math as ``block_apply`` kind 'A', but attention runs
    against the paged pool through ``attn_prefill_paged`` — cached prefix
    blocks provide the keys below the traced start offset and the tail's
    own k/v is scattered into the pool in place of the dense prefill-cache
    extraction.  Only the fully-paged tier uses this (no MoE / recurrent /
    SSD / ring / cross state exists to replay), so the FFN is always the
    dense MLP.  Chunked prefill (DESIGN.md §10) reuses this block per
    chunk — the traced offset means one compiled trace serves every chunk
    position of every prompt in the tail bucket."""
    h = _norm_apply(cfg, p["pre_norm"], x)
    y, cache = attn_prefill_paged(
        p["attn"],
        h,
        cache,
        bt_row,
        positions,
        cfg=_attn_cfg(cfg),
        seq_len=seq_len,
        window=window,
        rope_base=rope_base,
        compute_dtype=compute_dtype,
    )
    y = jax.lax.optimization_barrier(_tag(y, "block_out"))
    if cfg.post_norm:
        y = _norm_apply(cfg, p["post_attn_norm"], y)
    x = x + y
    h = _norm_apply(cfg, p["pre_mlp_norm"], x)
    y = mlp_apply(p["mlp"], h, cfg=_mlp_cfg(cfg), compute_dtype=compute_dtype)
    y = jax.lax.optimization_barrier(_tag(y, "block_out"))
    if cfg.post_norm:
        y = _norm_apply(cfg, p["post_mlp_norm"], y)
    return x + y, cache


def block_verify_paged(
    p,
    x,
    cache,
    block_tables,
    positions,
    *,
    cfg: ModelConfig,
    valid,
    window=None,
    rope_base=10000.0,
    compute_dtype=jnp.bfloat16,
):
    """Speculative multi-token verify for an attention ('A'/'D') block
    (DESIGN.md §8): the per-token math of ``block_decode`` at T = K+1
    tokens per row, with attention running scatter-before-gather against
    the paged pool (``attn_verify_paged`` / ``mla_verify_paged``).  Only
    the fully-paged tier verifies (no recurrent / SSD / ring / cross-kv
    state to roll back), so the FFN is always the dense MLP — MoE capacity
    competition across the K+1 in-flight tokens would break the one-pass
    == sequential-decode equivalence the controller relies on."""
    h = _norm_apply(cfg, p["pre_norm"], x)
    if cfg.use_mla:
        y, cache = mla_verify_paged(
            p["attn"],
            h,
            cache,
            block_tables,
            positions,
            cfg=_mla_cfg(cfg),
            valid=valid,
            rope_base=rope_base,
            compute_dtype=compute_dtype,
        )
    else:
        y, cache = attn_verify_paged(
            p["attn"],
            h,
            cache,
            block_tables,
            positions,
            cfg=_attn_cfg(cfg),
            valid=valid,
            window=window,
            rope_base=rope_base,
            compute_dtype=compute_dtype,
        )
    if cfg.post_norm:
        y = _norm_apply(cfg, p["post_attn_norm"], y)
    x = x + y
    h = _norm_apply(cfg, p["pre_mlp_norm"], x)
    y = mlp_apply(p["mlp"], h, cfg=_mlp_cfg(cfg), compute_dtype=compute_dtype)
    if cfg.post_norm:
        y = _norm_apply(cfg, p["post_mlp_norm"], y)
    return x + y, cache


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def block_cache_init(batch: int, max_len: int, cfg: ModelConfig, kind: str,
                     ring: bool = False, dtype=jnp.bfloat16):
    if kind == "M":
        return ssd_init_cache(batch, _ssd_cfg(cfg), dtype)
    if kind == "R":
        return rglru_init_cache(batch, _rglru_cfg(cfg))
    if cfg.use_mla:
        return mla_init_cache(batch, max_len, _mla_cfg(cfg), dtype)
    if ring and cfg.window and cfg.window < max_len:
        c = attn_init_cache(batch, cfg.window, _attn_cfg(cfg), dtype)
        # per-row ring positions: continuous batching gives every request its
        # own write offset, so the occupancy map is (B, W), not (W,)
        c["kv_pos"] = jnp.full((batch, cfg.window), -1, jnp.int32)
        return c
    return attn_init_cache(batch, max_len, _attn_cfg(cfg), dtype)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def _attn_decode_ring(pa, x, cache, pos, *, cfg: ModelConfig, rope_base, compute_dtype):
    """Ring-buffer local-attention decode: cache size = window W; slot =
    pos % W per row; stored kv positions (B, W) drive the mask (long_500k
    recurrentgemma).  ``pos`` scalar or (B,) — per-request ring offsets."""
    acfg = _attn_cfg(cfg)
    B = x.shape[0]
    H, K, hd = acfg.n_heads, acfg.n_kv_heads, acfg.head_dim
    W = cache["k"].shape[1]
    positions, per_row = attn_mod.decode_positions(pos, B)
    q = dense_apply(pa["q_proj"], x, compute_dtype=compute_dtype)
    k_new = dense_apply(pa["k_proj"], x, compute_dtype=compute_dtype)
    v_new = dense_apply(pa["v_proj"], x, compute_dtype=compute_dtype)
    if acfg.qk_norm:
        q = rmsnorm_apply(pa["q_norm"], q)
        k_new = rmsnorm_apply(pa["k_norm"], k_new)
    q = apply_rope(q, positions, rope_base)
    k_new = apply_rope(k_new, positions, rope_base)
    slot = jnp.mod(pos, W)
    cache = {
        "k": attn_mod.cache_update_rows(cache["k"], k_new, slot, per_row=per_row),
        "v": attn_mod.cache_update_rows(cache["v"], v_new, slot, per_row=per_row),
        "kv_pos": attn_mod.cache_update_rows(cache["kv_pos"], positions, slot, per_row=per_row),
    }
    kv_pos = cache["kv_pos"]  # (B, W)
    valid = (kv_pos >= 0) & (kv_pos <= positions) & (positions - kv_pos < W)
    mask = jnp.broadcast_to(valid[:, None, :], (B, 1, W))
    qh = q.reshape(B, 1, K, H // K, hd)
    out = attn_mod._qk_attn(qh, attn_mod.cache_read(cache["k"], compute_dtype),
                            attn_mod.cache_read(cache["v"], compute_dtype),
                            mask, scale=(acfg.query_scale or hd ** -0.5), cap=acfg.softcap)
    y = dense_apply(pa["o_proj"], out.reshape(B, 1, H, hd), n_in=2, compute_dtype=compute_dtype)
    return y, cache


def block_decode(
    p,
    x,
    cache,
    pos,
    *,
    cfg: ModelConfig,
    kind: str,
    window=None,
    rope_base=10000.0,
    compute_dtype=jnp.bfloat16,
    enc_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    dropless_moe: bool = False,
    block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any]:
    """``block_tables`` (B, max_blocks): paged-cache decode — attention and
    MLA caches arrive as (n_blocks, block, ...) pools resolved per row.  The
    recurrent/SSD states and the ring-buffer layout are O(1) per slot and
    keep their resident per-row layouts regardless (DESIGN.md §6)."""
    if kind == "M":
        h = _norm_apply(cfg, p["pre_norm"], x)
        y, cache = ssd_block_decode(
            p["ssd"], h, cache, cfg=_ssd_cfg(cfg), compute_dtype=compute_dtype
        )
        return x + y, cache

    if kind == "R":
        h = _norm_apply(cfg, p["pre_norm"], x)
        y, cache = rglru_block_decode(
            p["rglru"], h, cache, cfg=_rglru_cfg(cfg), compute_dtype=compute_dtype
        )
        x = x + y
    else:
        h = _norm_apply(cfg, p["pre_norm"], x)
        if cfg.use_mla:
            y, cache = mla_decode(p["attn"], h, cache, pos, cfg=_mla_cfg(cfg),
                                  rope_base=rope_base, compute_dtype=compute_dtype,
                                  block_tables=block_tables)
        elif "kv_pos" in cache:
            y, cache = _attn_decode_ring(p["attn"], h, cache, pos, cfg=cfg,
                                         rope_base=rope_base, compute_dtype=compute_dtype)
        else:
            y, cache = attn_decode(p["attn"], h, cache, pos, cfg=_attn_cfg(cfg), window=window,
                                   rope_base=rope_base, compute_dtype=compute_dtype,
                                   block_tables=block_tables)
        if cfg.post_norm:
            y = _norm_apply(cfg, p["post_attn_norm"], y)
        x = x + y
        if enc_kv is not None:
            h = _norm_apply(cfg, p["cross_norm"], x)
            y, _ = attn_decode(p["cross_attn"], h, None, pos, cfg=_attn_cfg(cfg),
                               rope_base=rope_base, compute_dtype=compute_dtype, kv=enc_kv)
            x = x + y

    h = _norm_apply(cfg, p["pre_mlp_norm"], x)
    if kind == "E":
        # dropless (scheduler) decode: a token's top-k experts are DISTINCT,
        # so with B single-token rows an expert sees at most B assignments —
        # capacity B guarantees no assignment ever drops.  Drop-free routing
        # makes each row's output independent of who else shares the slot
        # table: the invariant continuous batching needs for token-exactness
        # vs per-request static decode.  The classic uniform loop keeps the
        # bounded capacity (a static batch never mixes unrelated rows).
        if cfg.moe_impl == "ep" and _ep_active(cfg):
            # expert-parallel decode (DESIGN.md §12): experts sharded over
            # the EP axes, tokens routed by all_to_all; ``dropless`` sizes
            # the EP capacities at their worst-case bounds so the same
            # row-independence invariant holds
            from repro.models.moe_ep import moe_apply_ep

            y, _ = moe_apply_ep(p["moe"], h, cfg=_moe_cfg(cfg), compute_dtype=compute_dtype,
                                ep_axes=tuple(cfg.ep_axes), dropless=dropless_moe)
        else:
            if dropless_moe:
                cap = x.shape[0]
            else:
                cap = max(cfg.top_k, math.ceil(2.0 * x.shape[0] * cfg.top_k / cfg.n_experts))
            y, _ = moe_apply(p["moe"], h, cfg=_moe_cfg(cfg), compute_dtype=compute_dtype,
                             capacity=cap)
    else:
        y = mlp_apply(p["mlp"], h, cfg=_mlp_cfg(cfg), compute_dtype=compute_dtype)
    if cfg.post_norm:
        y = _norm_apply(cfg, p["post_mlp_norm"], y)
    return x + y, cache
