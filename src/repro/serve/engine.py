"""Batched serving: jit'd prefill + decode with a uniform-position KV cache.

The engine serves three kinds of param trees through the SAME forward code:

  float          — ordinary bf16/f32 leaves;
  quantize_tree  — SYMOG post-quantized floats (exact fixed-point values in
                   float representation — numerically the reference for the
                   packed path);
  pack_tree      — the ``Packed`` serving artifact: 2/4-bit mantissas in
                   int8 words plus one integer exponent per layer (or per
                   expert).  The layer stack dispatches those leaves to the
                   packed fixed-point matmul at every dense/einsum call site
                   (repro.models.quantized): Pallas on TPU — weights stream
                   HBM→VMEM at n_bits/16 of the bf16 bytes, the decode-side
                   realization of the paper's bit-shift dequantization.  Off
                   TPU the 'dense' backend densifies the tree ONCE at engine
                   construction (exact dequantization), so generation stays
                   token-identical to the quantize_tree params on any host
                   without re-paying the unpack every matmul.

``Packed`` is a registered pytree node, so jit closes over packed trees
like any other params; nothing is densified at rest on TPU.  The engine
also pins the attention backend (repro.kernels.dispatch): paged decode /
verify / tail-prefill run the fused ``paged_attention`` kernel on TPU and
the composed gather+softmax path elsewhere.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.packing import pack_int
from repro.models.attention import (
    KV_QMAX,
    block_scale_exp,
    cache_read,
    pool_block,
    quantize_fixed,
)
from repro.models.config import ModelConfig
from repro.models.lm import (
    PAGED_CACHE_LEAVES,
    PAGED_SCALE_LEAVES,
    decode_lm,
    prefill_lm,
    prefill_prefix_lm,
    scan_groups,
)
from repro.kernels.dispatch import (
    get_attention_backend,
    resolve_attention_backend,
    set_attention_backend,
)
from repro.models.quantized import (
    get_packed_backend,
    resolve_backend,
    set_packed_backend,
    tree_has_packed,
    unpack_params,
)
from repro.nn.sharding import current_mesh, make_rules, mesh_axis_size, shardings_for_tree
from repro.nn.tree import tree_bytes


def _prefill_blocks(pool, src, axis, p_blocks):
    """A batch-of-one prefill leaf cut into the pool's blocks.

    src: the prefill leaf, batch axis of size 1 at ``axis`` and a max_len
    length axis after it.  Returns (p_blocks, block, *feat) token-major
    blocks (one more leading layer axis when ``axis`` is 1), padded or cut
    to the bucket's span."""
    block = pool_block(pool, axis)
    src = jnp.squeeze(src, axis=axis)  # drop the batch-of-one axis
    need = p_blocks * block
    t = src.shape[axis]
    if need > t:
        pad = [(0, 0)] * src.ndim
        pad[axis] = (0, need - t)
        src = jnp.pad(src, pad)
    elif need < t:
        src = jax.lax.slice_in_dim(src, 0, need, axis=axis)
    return src.reshape(src.shape[:axis] + (p_blocks, block) + src.shape[axis + 1 :])


def _set_blocks(pool, blocks, ids, axis):
    """Write token-major blocks at physical ids, head-major where the pool is."""
    if pool.ndim - axis == 4:
        blocks = blocks.swapaxes(axis + 1, axis + 2)
    if axis == 0:
        return pool.at[ids].set(blocks)
    return pool.at[:, ids].set(blocks)


def _scatter_blocks(pool, src, bt_row, axis, p_blocks):
    """Write a batch-of-one prefill cache into the paged pool.

    pool: a paged pool leaf (models.attention.paged_pool_shape) — one more
    leading layer axis when ``axis`` is 1 (scan-stacked group).  bt_row
    (max_blocks,): the slot's PHYSICAL block ids; only the first
    ``p_blocks`` (the bucket's span — a static per-trace count) are written,
    and table entries past the allocated prefix are trash (0), so the
    bucket's padded tail lands in the trash block instead of real capacity.
    """
    src = _prefill_blocks(pool, src, axis, p_blocks).astype(pool.dtype)
    return _set_blocks(pool, src, bt_row[:p_blocks], axis)


def _scatter_blocks_quant(pool, exp_leaf, src, bt_row, axis, p_blocks):
    """Quantizing variant of ``_scatter_blocks`` for per-block SYMOG pools
    (DESIGN.md §11): dequantize the prefill leaf (float, or KV_F int8),
    calibrate each written block's exponent from its FIRST token, quantize
    every token under its block's scale, and scatter the int8 / packed-int4
    mantissas plus the exponent rows."""
    src = cache_read(_prefill_blocks(pool, src, axis, p_blocks), jnp.float32)
    bits = 4 if pool.shape[-1] * 2 == src.shape[-1] else 8
    qmax = KV_QMAX[bits]
    e = block_scale_exp(jax.lax.index_in_dim(src, 0, axis + 1, keepdims=False), qmax)
    q = quantize_fixed(src, jnp.expand_dims(e, axis + 1), qmax)
    if bits == 4:
        q = pack_int(q, 4)
    ids = bt_row[:p_blocks]
    exp_leaf = exp_leaf.at[ids].set(e) if axis == 0 else exp_leaf.at[:, ids].set(e)
    return _set_blocks(pool, q, ids, axis), exp_leaf


def filter_logits(logits, temperature, top_k: int):
    """The sampling distribution's logit transform — temperature scaling
    plus top-k masking.  ONE definition shared by the vanilla sampler
    (``SchedulerFns._sample``) and speculative rejection sampling
    (``serve/speculative.py``): acceptance must target exactly the
    distribution vanilla serve() draws from, so the transform must never
    fork."""
    scaled = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return scaled


class SchedulerFns:
    """Jitted continuous-batching traces for one (greedy, top_k) sampling
    config.  Owned by the ENGINE (scheduler_fns memo) — serve() builds a
    fresh Scheduler per call, and per-scheduler jit caches would recompile
    the decode step on every request wave.

    ``decode_step`` is the one shared ragged decode dispatch (paged block
    tables resolve each row's cache).  ``admit_step(bucket, block_size)``
    returns the fused prefill + block-scatter + first-token-sample admission
    trace for one power-of-two prompt bucket, compiled on first use and
    memoized: admission compiles O(log max_len) traces for a workload of
    arbitrarily many distinct prompt lengths (``admit_compiles`` counts the
    distinct traces built — the Scheduler surfaces it in stats).
    """

    def __init__(self, engine: "ServeEngine", *, greedy: bool, top_k: int):
        self._eng = engine
        cfg, cd = engine.cfg, engine.compute_dtype
        self._groups = scan_groups(cfg)

        def _sample(logits, seeds, base_key, temperature):
            # logits (B, V) fp32; seeds (B,) int32 — stream ids keyed by
            # (request, step) so slot placement can't change the draw
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            scaled = filter_logits(logits, temperature, top_k)
            keys = jax.vmap(lambda s: jax.random.fold_in(base_key, s))(seeds)
            return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)

        def _decode_step(
            params, caches, tokens, pos, active, seed0, block_tables, base_key, temperature
        ):
            # tokens (S,) — the previous step's output fed straight back as a
            # device handle; pos advances on-device (inactive rows frozen)
            # and seeds derive as seed0 + pos, so the host uploads nothing
            # per step beyond single-row table edits and downloads only the
            # sampled tokens.  The cache pool is DONATED: without aliasing,
            # XLA would copy the whole block pool every emitted token.
            logits, caches = decode_lm(
                params,
                caches,
                tokens[:, None],
                pos,
                cfg,
                compute_dtype=cd,
                active=active,
                block_tables=block_tables,
            )
            nxt = _sample(logits[:, -1, :].astype(jnp.float32), seed0 + pos, base_key, temperature)
            return nxt, pos + active.astype(jnp.int32), caches

        self._sample_fn = _sample
        self.decode_step = jax.jit(_decode_step, donate_argnums=(1,))
        self._admits: Dict[Any, Any] = {}
        self._admits_prefix: Dict[Any, Any] = {}
        self.admit_compiles = 0
        # tail/chunk traces alone (admit_compiles still counts BOTH kinds,
        # its historical contract); the telemetry chunk-trace counter reads
        # this so chunked-prefill recompiles are attributable separately
        self.prefix_compiles = 0
        self.cow_copy = jax.jit(self._build_cow(), donate_argnums=(0,))

    def decode_cache_size(self) -> int:
        """Compiled-signature count of the shared decode trace (jit cache
        size) — the scheduler's ``decode_trace_compiles`` telemetry reads
        the delta against its construction-time baseline.  0 when the jax
        version doesn't expose the probe (the counter then just stays flat,
        which the steady-state regression test treats as vacuous pass)."""
        try:
            return int(self.decode_step._cache_size())
        except Exception:
            return 0

    def admit_step(self, bucket: int, block_size: int):
        """The admission trace for one (bucket, block geometry) pair."""
        key = (int(bucket), int(block_size))
        if key not in self._admits:
            self._admits[key] = jax.jit(self._build_admit(*key), donate_argnums=(3,))
            self.admit_compiles += 1
        return self._admits[key]

    def admit_prefix_step(self, bucket: int, block_size: int):
        """The prefix-hit admission trace (tail-bucket prefill, DESIGN.md §7)
        for one (tail bucket, block geometry) pair — the traced start offset
        and real tail length keep this O(log max_len) traces like the miss
        path (both count into ``admit_compiles``)."""
        key = (int(bucket), int(block_size))
        if key not in self._admits_prefix:
            self._admits_prefix[key] = jax.jit(
                self._build_admit_prefix(*key), donate_argnums=(4,)
            )
            self.admit_compiles += 1
            self.prefix_compiles += 1
        return self._admits_prefix[key]

    def _build_cow(self):
        """Copy-on-write block clone: duplicate one physical pool row (every
        paged leaf, every layer) from ``src`` to ``dst``.  The scheduler
        invokes it when a prefix hit ends inside a partially-filled cached
        block: the new request gets a private copy it may append into while
        the source block keeps serving the cache (rows past the matched
        fill are junk in the copy — masked by the causal horizon until the
        owner overwrites them)."""
        groups = self._groups

        def _cow(caches, src, dst):
            out = {}
            for g in groups:
                axis = 1 if g.stacked else 0
                gsub = {}
                for j in range(len(g.unit)):
                    sub = {}
                    for name, leaf in caches[g.name][f"sub{j}"].items():
                        if g.paged[j] and (
                            name in PAGED_CACHE_LEAVES or name in PAGED_SCALE_LEAVES
                        ):
                            if axis == 0:
                                leaf = leaf.at[dst].set(leaf[src])
                            else:
                                leaf = leaf.at[:, dst].set(leaf[:, src])
                        sub[name] = leaf
                    gsub[f"sub{j}"] = sub
                out[g.name] = gsub
            return out

        return _cow

    def _build_admit_prefix(self, bucket: int, block_size: int):
        eng, sample = self._eng, self._sample_fn
        cfg, cd = eng.cfg, eng.compute_dtype

        def _admit(params, batch, length, start, caches, bt_row, seed, base_key, temperature):
            # tail-bucket prefill: tokens are the (1, bucket) right-padded
            # UNCACHED suffix; ``start`` (traced) is the cached-prefix
            # length, ``length`` the real tail length.  The tail's KV lands
            # in the pool inside the trace (paged scatter at start+i), so no
            # separate block scatter step exists on this path.
            logits, out = prefill_prefix_lm(
                params, batch, caches, bt_row, start, cfg, seq_len=length, compute_dtype=cd
            )
            first = sample(logits[:, -1, :].astype(jnp.float32), seed[None], base_key, temperature)
            return first[0], out

        return _admit

    def _build_admit(self, bucket: int, block_size: int):
        eng, groups, sample = self._eng, self._groups, self._sample_fn
        cfg, cd = eng.cfg, eng.compute_dtype
        offset = cfg.prefix_len if cfg.family == "vlm" else 0
        p_blocks = -(-(offset + bucket) // block_size)

        def _admit(params, batch, length, caches, bt_row, slot, seed, base_key, temperature):
            # bucketed prefill: tokens are (1, bucket) right-padded; ``length``
            # (traced) is the real prompt length, so one trace serves every
            # length in the bucket, samples at the last REAL position, and
            # writes only the bucket's blocks (padded tail -> trash block)
            logits, one = prefill_lm(
                params, batch, cfg, max_len=eng.max_len, compute_dtype=cd, seq_len=length
            )
            out = {}
            for g in groups:
                axis = 1 if g.stacked else 0
                gsub = {}
                for j in range(len(g.unit)):
                    dst = dict(caches[g.name][f"sub{j}"])
                    src = one[g.name][f"sub{j}"]
                    for name, leaf in src.items():
                        if g.paged[j] and name in PAGED_CACHE_LEAVES:
                            sname = name + "_scale"
                            if sname in dst:
                                dst[name], dst[sname] = _scatter_blocks_quant(
                                    dst[name], dst[sname], leaf, bt_row, axis, p_blocks
                                )
                            else:
                                dst[name] = _scatter_blocks(dst[name], leaf, bt_row, axis, p_blocks)
                        else:
                            dst[name] = jax.lax.dynamic_update_slice_in_dim(
                                dst[name], leaf.astype(dst[name].dtype), slot, axis
                            )
                    gsub[f"sub{j}"] = dst
                out[g.name] = gsub
            first = sample(logits[:, -1, :].astype(jnp.float32), seed[None], base_key, temperature)
            return first[0], out

        return _admit


@dataclasses.dataclass
class ServeEngine:
    cfg: ModelConfig
    params: Any
    max_len: int
    compute_dtype: Any = jnp.bfloat16
    # multi-device serving (DESIGN.md §12): a (data, model) Mesh shards the
    # packed weight words over the nn/sharding logical rules and the paged
    # KV pool over KV heads; None (and a 1-device mesh) serves exactly as
    # before.  Like the kernel backends, the mesh is PINNED at construction
    # — every jitted trace runs under ``jax.set_mesh(self.mesh)``.
    mesh: Any = None
    sharding_profile: str = ""  # defaults to cfg.sharding_profile

    def __post_init__(self):
        cfg, cd = self.cfg, self.compute_dtype
        self.packed = tree_has_packed(self.params)
        # Both backends are baked into the jitted traces at first call; pin
        # them NOW so later set_*_backend() calls can't desync a cached
        # trace from the globals (construct a new engine to switch).
        self.backend = resolve_backend()
        self.attn_backend = resolve_attention_backend()
        if self.mesh is None and current_mesh() is not None:
            self.mesh = jax.sharding.get_mesh()  # constructing under jax.set_mesh pins it
        self.rules = None
        if self.mesh is not None:
            self.rules = make_rules(self.mesh, self.sharding_profile or cfg.sharding_profile)
            # place every param leaf (Packed int8 words flatten as <p>/0 and
            # match their parent's rule; per-layer exponents ride along) —
            # the admission/decode traces then consume pre-sharded weights
            # and GSPMD propagates the layout through the forward
            self.params = jax.device_put(self.params, shardings_for_tree(self.rules, self.params))
        if self.packed and self.backend == "dense":
            # Off-TPU there is no fused dequant kernel and unpack-then-dot
            # re-pays the unpack every matmul — slower than float serving.
            # Densify ONCE: exact dequantization, token-identical output.
            import logging

            logging.getLogger(__name__).warning(
                "packed params with backend 'dense': densifying once at engine "
                "construction (exact; avoids per-call unpack overhead off-TPU)"
            )
            self.params = unpack_params(self.params)

        @jax.jit
        def _prefill(params, batch):
            return prefill_lm(params, batch, cfg, max_len=self.max_len, compute_dtype=cd)

        @jax.jit
        def _decode(params, caches, tokens, pos):
            return decode_lm(params, caches, tokens, pos, cfg, compute_dtype=cd)

        self._prefill = _prefill
        self._decode = _decode
        self._sched_fns: Dict[Any, SchedulerFns] = {}
        self._cache_shapes = None
        self._fingerprint = None

    @property
    def kv_quant_bits(self) -> int:
        """Wordlength of the per-block SYMOG paged KV pool: 8 (int8_fp) or
        4 (int4_fp) for decoder-family engines, 0 otherwise.  Non-decoder
        families keep the legacy rule — dense/ring caches at KV_F int8 for
        int8_fp and compute dtype elsewhere (int4_fp degrades to float
        there), so nothing outside the paged decoder stack changes."""
        if self.cfg.family != "decoder":
            return 0
        return {"int8_fp": 8, "int4_fp": 4}.get(self.cfg.kv_cache_dtype, 0)

    def params_fingerprint(self) -> str:
        """Within-process identity of the served artifact, namespacing the
        prefix cache (DESIGN.md §7).  quantize_tree and pack_tree params
        produce different KV bytes from the same tokens, so their cached
        blocks must never cross-share: the fingerprint hashes the pytree
        structure (``Packed`` nodes appear in the treedef), per-leaf
        shapes/dtypes, and the tree's object identity — deliberately
        conservative (two numerically equal trees fingerprint apart; a
        false split only costs cache hits, a false merge would corrupt
        generations)."""
        if self._fingerprint is None:
            import hashlib

            leaves, treedef = jax.tree_util.tree_flatten(self.params)
            h = hashlib.sha1()
            h.update(repr(treedef).encode())
            h.update(f"packed={self.packed} id={id(self.params)}".encode())
            for leaf in leaves:
                h.update(f"{getattr(leaf, 'shape', ())}/{getattr(leaf, 'dtype', '')};".encode())
            self._fingerprint = h.hexdigest()[:16]
        return self._fingerprint

    def prefill_cache_shapes(self):
        """ShapeDtypeStruct tree of one request's prefill caches (lazy
        eval_shape, no FLOPs) — the Scheduler derives the paged pool and
        resident slot-table layouts from it.  Memoized: tracing the prefill
        per serve() call would dominate short workloads."""
        if self._cache_shapes is None:
            cfg = self.cfg
            dummy = {"tokens": jnp.zeros((1, 1), jnp.int32)}
            if cfg.family == "encdec":
                dummy["frames"] = jnp.zeros((1, cfg.encoder_len, cfg.d_model), jnp.float32)
            if cfg.family == "vlm":
                dummy["patches"] = jnp.zeros((1, cfg.prefix_len, cfg.d_model), jnp.float32)
            _, self._cache_shapes = jax.eval_shape(self._prefill, self.params, dummy)
        return self._cache_shapes

    def scheduler_fns(self, *, greedy: bool, top_k: int) -> SchedulerFns:
        """Memoized SchedulerFns per (greedy, top_k) — the only sampling
        knobs that change a trace; temperature and the PRNG key are traced
        arguments."""
        key = (bool(greedy), int(top_k))
        if key not in self._sched_fns:
            self._sched_fns[key] = SchedulerFns(self, greedy=greedy, top_k=top_k)
        return self._sched_fns[key]

    def speculative_fns(self, *, greedy: bool, top_k: int):
        """Memoized draft/verify traces (DESIGN.md §8), same memo contract
        as ``scheduler_fns`` — the traces close over this TARGET engine's
        config only; draft params ride in as call arguments, so one memo
        serves every draft artifact."""
        from repro.serve.speculative import SpeculativeFns

        key = ("spec", bool(greedy), int(top_k))
        if key not in self._sched_fns:
            self._sched_fns[key] = SpeculativeFns(self, greedy=greedy, top_k=top_k)
        return self._sched_fns[key]

    def _with_backend(self, fn, *args):
        prev_p, prev_a = get_packed_backend(), get_attention_backend()
        set_packed_backend(self.backend)
        set_attention_backend(self.attn_backend)
        try:
            if self.mesh is not None:
                # the ambient mesh is part of the pinned trace environment:
                # moe_ep routing and the paged-attention head-slicing
                # wrapper both branch on current_mesh() while tracing
                with jax.set_mesh(self.mesh):
                    return fn(*args)
            return fn(*args)
        finally:
            set_packed_backend(prev_p)
            set_attention_backend(prev_a)

    def model_shards(self) -> int:
        """Size of the mesh's ``model`` axis (1 off-mesh) — the tensor/KV-
        head/expert parallel degree the §12 pool math is over."""
        return mesh_axis_size(self.mesh, "model")

    @classmethod
    def from_symog(
        cls,
        cfg: ModelConfig,
        params,
        symog_state,
        symog_cfg,
        *,
        max_len: int,
        compute_dtype=jnp.bfloat16,
        mesh=None,
        sharding_profile: str = "",
    ) -> "ServeEngine":
        """Pack a SYMOG-trained float tree and serve the Packed artifact."""
        from repro.core.symog import pack_tree

        tree = pack_tree(params, symog_state, symog_cfg)
        return cls(
            cfg,
            tree,
            max_len=max_len,
            compute_dtype=compute_dtype,
            mesh=mesh,
            sharding_profile=sharding_profile,
        )

    def weight_bytes(self) -> int:
        """Resident param bytes (Packed leaves count their int8 words — the
        number the serving bandwidth math in DESIGN.md §2 is about)."""
        return tree_bytes(self.params)

    def prefill(self, batch: Dict[str, jax.Array]):
        return self._with_backend(self._prefill, self.params, batch)

    def decode(self, caches, tokens, pos):
        return self._with_backend(self._decode, self.params, caches, tokens, pos)

    def capabilities(self):
        """Structural serving capabilities of this engine with reasons —
        ``{fully_paged, prefix_cache, chunked_prefill, speculative,
        ep_moe}``, each a truthy/falsy ``serve.Capability``.  The one source
        of truth the launcher's inert-flag warnings and the scheduler's own
        eligibility decisions both read (DESIGN.md §7/§8/§10/§12)."""
        from repro.serve.config import capabilities

        return capabilities(self)

    def serve(
        self,
        requests: Sequence[Any],
        config=None,
        *,
        return_scheduler: bool = False,
        **legacy,
    ):
        """Continuous-batching serve: schedule ``requests`` (scheduler.Request)
        onto a ragged paged-decode slot table per ``config`` (a
        ``serve.ServeConfig`` — sampling, block geometry, prefix cache §7,
        speculative decoding §8, chunked prefill + streaming §10 all live
        there; ``config=None`` means all defaults).  Returns Completions in
        submission order (and the drained Scheduler when asked — slot events
        and step stats for tests/benchmarks).

        The pre-redesign keyword form ``serve(reqs, n_slots=..., ...)``
        still works but emits a ``DeprecationWarning``; pass a ServeConfig.
        """
        from repro.serve.config import ServeConfig
        from repro.serve.scheduler import serve_requests

        if legacy:
            if config is not None:
                raise TypeError("pass either a ServeConfig or legacy keyword args, not both")
            warnings.warn(
                "serve(requests, n_slots=..., ...) is deprecated; pass "
                "serve(requests, serve.ServeConfig(...))",
                DeprecationWarning,
                stacklevel=2,
            )
            config = ServeConfig(**legacy)
        comps, sched = serve_requests(self, requests, config)
        return (comps, sched) if return_scheduler else comps

    def serve_async(self, config=None):
        """An ``AsyncServeEngine`` over this engine: submit/stream/cancel
        from asyncio coroutines while a drive loop steps the scheduler in a
        worker thread (DESIGN.md §10).  Use as an async context manager."""
        from repro.serve.async_engine import AsyncServeEngine

        return AsyncServeEngine(self, config)

    def generate(self, batch: Dict[str, jax.Array], steps: int) -> jax.Array:
        """Greedy continuation of a batched prompt; returns (B, steps).

        Compatibility wrapper over ``serve``: each row becomes one request
        (fixed ``steps`` budget, no EOS), scheduled onto B slots — so the
        classic API now exercises the ragged paged decode path."""
        from repro.serve.config import ServeConfig
        from repro.serve.scheduler import Request

        tokens = np.asarray(batch["tokens"])
        B = tokens.shape[0]
        reqs = []
        for b in range(B):
            extras = {k: np.asarray(v[b : b + 1]) for k, v in batch.items() if k != "tokens"}
            reqs.append(Request(tokens=tokens[b], max_new_tokens=steps, extras=extras or None))
        comps = self.serve(reqs, ServeConfig(n_slots=B))
        if any(len(c.tokens) != steps for c in comps):
            raise ValueError(f"max_len={self.max_len} too small for {steps} steps")
        return jnp.asarray(np.stack([np.asarray(c.tokens, np.int32) for c in comps]))

    def generate_static(self, batch: Dict[str, jax.Array], steps: int) -> jax.Array:
        """The pre-scheduler static loop: one uniform-position batch with
        dense per-row caches, every request decoded for exactly ``steps``
        tokens.  Kept as the reference oracle for scheduler token-exactness
        tests (paged vs dense) and as the baseline the continuous-batching
        throughput benchmark is measured against."""
        tokens = batch["tokens"]
        B, T = tokens.shape
        logits, caches = self.prefill(batch)
        offset = self.cfg.prefix_len if self.cfg.family == "vlm" else 0
        cur = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out = [cur]
        for i in range(steps - 1):
            logits, caches = self.decode(caches, cur, jnp.int32(offset + T + i))
            cur = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            out.append(cur)
        return jnp.concatenate(out, axis=1)


def greedy_generate(
    cfg: ModelConfig,
    params,
    batch,
    steps: int,
    max_len: int,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    return ServeEngine(cfg, params, max_len, compute_dtype).generate(batch, steps)
