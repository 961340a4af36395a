"""Operations and bytes the serving path's work needs, from shapes and
from what each dispatch really carried: live rows, real prompt or chunk
tokens, each row's real context, rows routed to experts.  Padding to
buckets, to slot counts or to dropless expert capacity is not work, so a
change that removes it reads as a higher share of the roofline and never
as more work.

Every function returns ``(ops, bytes)``.  Activations are bf16 (2 bytes),
packed weights ``n_bits / 8`` bytes a value, int8 pools 1 byte a value plus
an int32 exponent per (block, KV head), bf16 pools 2 bytes a value.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Tuple

ACT_BYTES = 2


def packed_matmul(m: int, k: int, n: int, n_bits: int) -> Tuple[float, float]:
    """y (m, n) = x (m, k) @ W (k, n) with W packed at n_bits: 2mkn ops;
    the packed weight read once, x read and y written in bf16."""
    ops = 2.0 * m * k * n
    return ops, k * n * n_bits / 8 + (m * k + m * n) * ACT_BYTES


def packed_expert_matmul(routed_rows: int, experts: int, k: int, n: int,
                         n_bits: int) -> Tuple[float, float]:
    """The per-expert form: ``routed_rows`` (token, expert) assignments in
    all, over ``experts`` expert weights of (k, n).  Every expert's weight
    counts as read: with tens of routed rows per step nearly every expert
    is hit, and which ones are is not known on the host."""
    ops = 2.0 * routed_rows * k * n
    return ops, experts * k * n * n_bits / 8 + routed_rows * (k + n) * ACT_BYTES


def kv_value_bytes(pool: str) -> float:
    return {"bf16": 2.0, "int8_fp": 1.0, "int4_fp": 0.5}[pool]


def paged_attention(query_positions: Iterable[Tuple[int, int]], *, n_heads: int,
                    n_kv_heads: int, head_dim: int, pool: str,
                    block: int) -> Tuple[float, float]:
    """One paged-attention call over rows given as (start, tokens): each
    row's ``tokens`` queries sit at positions start .. start+tokens-1 and
    attend causally, so query t sees start + t + 1 keys.  Decode rows are
    (context - 1, 1).  Ops: QK^T and PV, 4 * heads * head_dim per key.
    Bytes: the row's keys and values read once per KV head, their block
    exponents on a quantized pool, q read and the output written."""
    ops = by = 0.0
    kvb = kv_value_bytes(pool)
    for start, tokens in query_positions:
        keys = start + tokens
        seen = tokens * start + tokens * (tokens + 1) / 2
        ops += 4.0 * n_heads * head_dim * seen
        by += 2 * keys * n_kv_heads * head_dim * kvb
        if pool != "bf16":
            by += 2 * math.ceil(keys / block) * n_kv_heads * 4
        by += 2 * tokens * n_heads * head_dim * ACT_BYTES
    return ops, by


def attention_projections(spec: dict) -> List[Tuple[int, int]]:
    """(k, n) of the packed attention projections of one layer."""
    D, H, K, hd = (spec["hidden_size"], spec["num_attention_heads"],
                   spec["num_key_value_heads"], spec["head_dim"])
    return [(D, H * hd), (D, K * hd), (D, K * hd), (H * hd, D)]


def ffn_projections(spec: dict) -> List[Tuple[int, int]]:
    """(k, n) of the dense MLP projections of one layer (none for MoE)."""
    if spec.get("num_experts", 0):
        return []
    D, F = spec["hidden_size"], spec["intermediate_size"]
    return [(D, F), (D, F), (F, D)]


def expert_projections(spec: dict) -> List[Tuple[int, int]]:
    if not spec.get("num_experts", 0):
        return []
    D, Fe = spec["hidden_size"], spec["moe_intermediate_size"]
    return [(D, Fe), (D, Fe), (Fe, D)]


def head_is_packed_matmul(spec: dict) -> bool:
    """An untied head runs the packed matmul; a tied one reads the
    embedding back through XLA."""
    return not spec["tie_word_embeddings"]


def fixedpoint_matmul_calls(spec: dict, tokens: int, head_rows: int) -> List[Tuple[float, float]]:
    """(ops, bytes) of every packed-matmul call of one dispatch over
    ``tokens`` real rows (decode rows, or a prompt's or chunk's real
    tokens), ``head_rows`` of which reach the output head."""
    bits = spec["weight_bits"]
    per_layer = [packed_matmul(tokens, k, n, bits)
                 for k, n in attention_projections(spec) + ffn_projections(spec)]
    per_layer += [packed_expert_matmul(tokens * spec["num_experts_per_tok"],
                                       spec["num_experts"], k, n, bits)
                  for k, n in expert_projections(spec)]
    calls = per_layer * spec["num_hidden_layers"]
    if head_is_packed_matmul(spec) and head_rows:
        calls.append(packed_matmul(head_rows, spec["hidden_size"], spec["vocab_size"], bits))
    return calls


def paged_attention_calls(spec: dict, rows: Iterable[Tuple[int, int]], pool: str,
                          block: int) -> List[Tuple[float, float]]:
    """(ops, bytes) of the paged-attention calls of one dispatch, one per
    layer, over rows given as (start, tokens)."""
    call = paged_attention(list(rows), n_heads=spec["num_attention_heads"],
                           n_kv_heads=spec["num_key_value_heads"],
                           head_dim=spec["head_dim"], pool=pool, block=block)
    return [call] * spec["num_hidden_layers"]


def least_seconds(calls: Iterable[Tuple[float, float]], peak: dict) -> float:
    """Sum over calls of the least time each could take on the chip: the
    larger of its ops at the bf16 peak and its bytes at the HBM peak."""
    return sum(max(o / peak["bf16_flops"], b / peak["hbm_bytes_per_s"]) for o, b in calls)


def active_matmul_params(spec: dict) -> int:
    """Weights one token multiplies with: attention and FFN (the routed
    top-k experts and the router for MoE) in every layer, and the head."""
    D = spec["hidden_size"]
    per_layer = sum(k * n for k, n in attention_projections(spec) + ffn_projections(spec))
    if spec.get("num_experts", 0):
        per_layer += spec["num_experts_per_tok"] * sum(k * n for k, n in expert_projections(spec))
        per_layer += D * spec["num_experts"]
    return per_layer * spec["num_hidden_layers"] + D * spec["vocab_size"]


def decode_model_flops(spec: dict, contexts: Iterable[int]) -> float:
    """Model FLOPs of one decode step: 2 x active parameters per live row,
    plus attention at each row's real context (keys attended)."""
    per_row = 2.0 * active_matmul_params(spec)
    att = 4.0 * spec["num_attention_heads"] * spec["head_dim"] * spec["num_hidden_layers"]
    return sum(per_row + att * c for c in contexts)
