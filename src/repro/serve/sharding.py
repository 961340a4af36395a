"""Mesh placement for the serving stack (DESIGN.md §12).

One place decides where every serving array lives on a ``(data, model)``
mesh:

  * packed/float **param** leaves follow the repo's path-based logical
    rules (``nn/sharding.py`` — heads/kv_heads/mlp/vocab/expert over
    ``model``, with the shape-aware divisibility fallback);
  * paged KV **pool data leaves** shard their KV-head axis over the rules'
    ``kv_heads`` mapping — each model shard holds its head slice of every
    physical block, so pool capacity scales with the mesh;
  * **scale leaves** (per-(block, KV-head) SYMOG exponents, §11), **block
    tables** and all resident per-slot state are **allocated replicated**:
    they are bookkeeping whose bytes are negligible next to the pool, and
    replicating them keeps the scheduler's single-row ``.at[]`` edits
    mesh-oblivious.  (XLA's sharding propagation may later co-shard scale
    exponents with their pool leaf on the trailing KV-head axis — a strict
    refinement of the same head-only layout, and the byte accounting below
    stays a valid upper bound);
  * MLA rank-space pools (``c_kv``/``k_rope`` — no KV-head axis) replicate:
    their per-token bytes are already compressed by the low-rank factor.

The byte-accounting helpers double as the ``serve_sharded_capacity`` bench
model, so the committed floor and the scheduler's actual placement can
never disagree about what is sharded.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.sharding import ShardingRules


def pool_head_shards(rules: ShardingRules, shape: Sequence[int], axis: int) -> int:
    """How many ways a paged data-pool leaf's KV-head axis shards under
    ``rules`` (1 = replicated).  ``shape`` is the pool leaf shape —
    head-major ``(n_blocks, K, block, hd)`` at ``axis``=0, one leading
    layer dim at ``axis``=1; MLA rank-space leaves ``(n_blocks, block, r)``
    have no KV-head axis and never shard.  Applies the same divisibility
    fallback as the param rules."""
    if len(shape) - axis != 4:
        return 1  # MLA c_kv/k_rope: no KV-head axis
    mapped = rules.axis_map.get("kv_heads")
    if mapped is None:
        return 1
    axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
    size = 1
    for a in axes:
        size *= rules.mesh.shape[a]
    return size if size > 1 and shape[axis + 1] % size == 0 else 1


def pool_pspec(rules: ShardingRules, shape: Sequence[int], axis: int) -> P:
    """PartitionSpec for one paged data-pool leaf: KV-head axis over the
    ``kv_heads`` mesh mapping when it divides, replicated otherwise."""
    if pool_head_shards(rules, shape, axis) == 1:
        return P()
    mapped = rules.axis_map["kv_heads"]
    spec = [None] * len(shape)
    spec[axis + 1] = mapped if isinstance(mapped, str) else tuple(mapped)
    return P(*spec)


def pool_sharding(
    mesh: Optional[Mesh], rules: Optional[ShardingRules], shape: Sequence[int], axis: int
) -> Optional[NamedSharding]:
    """NamedSharding for one paged data-pool leaf (None off-mesh)."""
    if mesh is None or rules is None:
        return None
    return NamedSharding(mesh, pool_pspec(rules, shape, axis))


def pool_bytes_per_device(
    engine, block_size: int, n_blocks: int, *, model_shards: int = 0
) -> Tuple[int, int]:
    """(total pool bytes, per-device resident pool bytes) for ``engine``'s
    paged-pool geometry — data leaves divided by their head-shard count,
    scale leaves counted replicated (the §12 placement).  With
    ``model_shards`` > 0 the head-shard count is modeled for a hypothetical
    mesh of that size instead of the engine's own rules — the bench uses
    this to price an 8-way pool without owning 8 devices."""
    import numpy as np

    from repro.models.attention import paged_pool_shape
    from repro.models.lm import PAGED_CACHE_LEAVES, scan_groups

    shapes = engine.prefill_cache_shapes()
    qbits = engine.kv_quant_bits
    n_phys = n_blocks + 1
    total = per_dev = 0
    for g in scan_groups(engine.cfg):
        axis = 1 if g.stacked else 0
        for j in range(len(g.unit)):
            for name, sd in shapes[g.name][f"sub{j}"].items():
                if not (g.paged[j] and name in PAGED_CACHE_LEAVES):
                    continue
                lead, feat = sd.shape[:axis], sd.shape[axis + 2 :]
                if qbits and len(feat):
                    if qbits == 4:
                        feat = feat[:-1] + (feat[-1] // 2,)
                    shape = paged_pool_shape(lead, n_phys, block_size, feat)
                    data_b = int(np.prod(shape))  # int8 words
                    scale_b = int(np.prod(lead + (n_phys,) + feat[:-1])) * 4
                else:
                    shape = paged_pool_shape(lead, n_phys, block_size, feat)
                    data_b = int(np.prod(shape)) * sd.dtype.itemsize
                    scale_b = 0
                if model_shards:
                    K = feat[0] if len(feat) == 2 else 1
                    shards = model_shards if (len(feat) == 2 and K % model_shards == 0) else 1
                else:
                    rules = getattr(engine, "rules", None)
                    shards = pool_head_shards(rules, shape, axis) if rules else 1
                total += data_b + scale_b
                per_dev += data_b // shards + scale_b
    return total, per_dev
