"""Continuous-batching request scheduler over ``ServeEngine`` with a paged
KV-cache block pool.

The engine's static ``generate_static`` loop serves one fixed batch at a
uniform position: every slot owns a dense ``max_len`` cache row, so device
capacity is bounded by the WORST-CASE request, not the workload.  With
2-bit packed weights the KV cache dominates resident HBM at serving time,
which makes that bound the capacity ceiling.  This module is the classic
continuous-batching loop (Orca-style iteration-level scheduling) on a
vLLM-style paged cache:

  * a FIFO **request queue** (``submit``) with optional arrival times in
    decode-step units; admission takes the first DUE request of the
    highest ``Request.priority`` (FIFO among equal-priority due requests;
    a not-yet-due head never blocks due requests behind it);
  * a **slot table** of ``n_slots`` rows sharing one jitted decode step;
    each row carries its own position, so the batch is ragged;
  * a **block pool**: attention-family caches live in shared
    ``(n_blocks, block, ...)`` pools; row b resolves position t through a
    device ``(S, max_blocks)`` block table (gather for reads, flat scatter
    for the per-row write).  Blocks are allocated on demand as a request's
    position crosses a block boundary, and EVICTION returns them to the
    free list immediately — capacity scales with live tokens, not with
    slots × max_len.  Recurrent/SSD states, conv windows, ring buffers and
    encdec cross-kv keep their fixed-size per-row layouts
    (``GroupSpec.paged`` decides, not scheduler special-casing);
  * **admission**: a free slot pops the queue, allocates the prompt's
    blocks, and runs ONE fused prefill+block-scatter+sample dispatch.
    Prompts are right-padded to power-of-two **buckets** (a traced real
    length masks the non-causal couplings), so admission compiles
    O(log max_len) traces instead of one per distinct prompt length
    (``stats['admission_traces']`` counts the distinct trace shapes this
    run used; ``stats['admission_trace_compiles']`` the ones built fresh —
    0 on a warm engine, traces are engine-memoized);
  * **chunked prefill** (``prefill_chunk > 0``, DESIGN.md §10): instead of
    one whole-bucket prefill stalling every decoding row, admission runs
    the prompt as a sequence of tail-prefill chunks — ONE chunk per
    scheduler step, in a mixed batch alongside the live decode dispatch —
    through the §7 traced-start-offset trace (a chunk IS a tail prefill
    with ``start = tokens done so far``).  The pool KV after the last
    chunk is bit-identical to the one-shot prefill, so token streams never
    change; only the latency shape does (long-prompt admission no longer
    adds a whole-prompt stall to neighbors' inter-token latency).  A
    prefilling slot holds its blocks but keeps its DEVICE table row zeroed
    until the final chunk — the shared decode dispatch writes through any
    populated row, so publishing early would let a concurrent decode step
    corrupt freshly prefilled blocks; chunks address the pool through a
    host-built row instead.  Fully-paged tier only (the tail-prefill trace
    exists there); elsewhere the knob is accepted and inert;
  * **preemption**: if the pool is exhausted when a request needs its next
    block, the lowest-priority (youngest among ties) live request is
    evicted, its blocks freed, and the request requeued at the front for a
    from-scratch restart.  Restarts are token-exact: greedy decode is
    deterministic and sampled streams are keyed by (request index, step),
    so a replay draws the same tokens;
  * **eviction**: a row that emits ``eos_id`` or exhausts its budget frees
    its blocks and its block-table row is zeroed — the reserved trash
    block (physical row 0) absorbs the dead row's writes until the slot is
    reused, so no pool-wide revert pass is needed;
  * **cancellation** (``cancel(idx)``): a queued request is dropped; a
    live one is torn down mid-stream — blocks return to the pool
    IMMEDIATELY (same ``_release`` path as eviction, so the trash-block
    redirect keeps the shared decode dispatch safe) and the partial output
    is returned as a ``finish_reason='cancelled'`` Completion.  Surviving
    rows are untouched: row independence (the §6 active-mask contract)
    means a neighbor's teardown never perturbs a live stream;
  * **streaming**: per-token callbacks (``ServeConfig.on_token`` or
    per-request via ``submit``) fire as tokens are committed, in stream
    order; a preempted request's replay is deduplicated against what was
    already streamed (replays are token-exact, so the count suffices);
  * **sampling**: greedy when ``temperature <= 0``; otherwise temperature /
    top-k sampling keyed by (request index, step) — NOT by slot — so a
    fixed seed reproduces token streams regardless of slot placement, and
    identically across ``quantize_tree`` and ``pack_tree`` params (whose
    logits are bit-equal on the unpack backend);
  * **prefix cache** (``prefix_cache=True``, DESIGN.md §7): admission first
    matches the prompt against a radix index of cached prompt blocks
    (``serve/prefixcache.py``).  A hit ACQUIRES the matched blocks into the
    new table (refcounted sharing, no recompute, no new allocation), COWs a
    partially-matched boundary block, and prefills only the uncached tail
    bucket with a traced start offset.  Only the fully-paged tier shares —
    an all-attention decoder whose every cache leaf lives in the block pool
    — because non-paged per-row state (recurrent h / conv, SSD state, ring
    buffers, cross-kv) cannot be pinned under two slots, and MoE capacity
    competition couples tokens across the whole prompt; other families
    silently bypass (every request is a miss, nothing is indexed).
    Eviction order under pressure: cached-but-idle blocks are reclaimed
    (LRU, inside ``BlockPool.alloc``) BEFORE any live request is preempted.

All knobs arrive as ONE validated ``serve.ServeConfig`` (DESIGN.md §10);
the legacy keyword-argument constructor still works but warns.  Everything
device-side runs through engine-owned jitted traces (DESIGN.md §6).  Slot
state (tokens/positions/active/seed bases/block tables) lives on device;
the host loop's only download per step is the sampled token vector it
needs for EOS and budget bookkeeping.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import sys
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec

from repro.distributed.fault import StepTimeMonitor
from repro.models.attention import paged_pool_shape
from repro.models.lm import PAGED_CACHE_LEAVES, scan_groups
from repro.obs import (
    NULL_SPAN,
    NULL_TRACER,
    MetricsRegistry,
    RingLog,
    StatsView,
    StepTracer,
    log_buckets,
)
from repro.obs.profiling import make_profile_window
from repro.serve.blockpool import BlockPool
from repro.serve.config import ServeConfig
from repro.serve.prefixcache import PrefixCache


@dataclasses.dataclass
class Request:
    """One generation request.  ``tokens`` is the (T,) prompt."""

    tokens: Any
    max_new_tokens: int = 16
    eos_id: int = -1  # -1: never emitted
    arrival: int = 0  # earliest decode step at which admission may happen
    priority: int = 0  # higher admits first among due requests; preempted last
    extras: Optional[Dict[str, Any]] = None  # encdec: frames (1,S,D); vlm: patches


@dataclasses.dataclass
class Completion:
    index: int  # submission order
    tokens: List[int]  # generated ids (incl. the eos token if emitted)
    prompt_len: int
    finish_reason: str  # 'eos' | 'length' | 'cancelled'
    slot: int
    arrival: int
    admitted_step: int  # last admission (preempted requests restart)
    finished_step: int
    first_token_step: int = -1  # step the first token was sampled (== admitted_step
    # for one-shot admission; later for chunked prefills; -1 if never sampled)
    spec_steps: int = 0  # speculative draft/verify rounds this request rode
    spec_tokens: int = 0  # tokens committed by those rounds (accepted + bonus)
    # lifecycle timeline (DESIGN.md §13): ordered (event, step) records —
    # submit/admit/chunk/token/preempt/finish/cancel.  'token' entries mark
    # DELIVERY: a preemption replay re-delivers nothing, so their count is
    # exactly len(tokens) whatever the slot history was
    timeline: List[Tuple[str, int]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Slot:
    index: int
    eos_id: int
    budget: int  # max tokens this slot may emit (max_len-clamped)
    prompt: np.ndarray
    req: Request  # kept for preemption requeue
    out: List[int]
    admitted_step: int
    pos: int  # host mirror of the device position (next cache write)
    blocks: List[int]  # logical block ids, in table order
    first_token_step: int = -1
    # chunked-prefill state (DESIGN.md §10): while ``prefilling`` the device
    # table row stays ZEROED (decode writes land in the trash block) and
    # chunks address the pool through the host-built ``row``
    prefilling: bool = False
    done: int = 0  # prompt tokens whose KV is resident (chunk start offset)
    row: Optional[np.ndarray] = None  # host physical-id table row

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def fully_paged_tier(engine, *, allow_mla: bool = False) -> bool:
    """True iff EVERY cache leaf of every group pages into the block pool —
    the structural precondition the prefix cache (DESIGN.md §7), the
    speculative controller (§8) and chunked prefill (§10) share.  Holds for
    all-attention decoders only: vlm's per-request patch prefix, encdec
    cross-kv, recurrent/SSD/ring per-row state and MoE capacity coupling
    all fail it.  Quantized KV pools (int8_fp/int4_fp) are tier-ELIGIBLE
    since DESIGN.md §11: per-block scales are calibrated once at the
    block's first write and never re-rounded, and every admission attends
    the quantized pool itself, so hit/miss/chunked traces stay
    bit-identical — the pool is its own oracle.  ``allow_mla``: MLA's
    compressed c_kv/k_rope leaves do page and the speculative verify
    implements the absorbed multi-token form, so §8 admits MLA where
    §7/§10 do not.  ``engine.capabilities()`` wraps this test with
    per-clause reasons."""
    cfg = engine.cfg
    if cfg.family != "decoder" or cfg.moe or (cfg.use_mla and not allow_mla):
        return False
    shapes = engine.prefill_cache_shapes()
    for g in scan_groups(cfg):
        for j in range(len(g.unit)):
            for name in shapes[g.name][f"sub{j}"]:
                if not (g.paged[j] and name in PAGED_CACHE_LEAVES):
                    return False
    return True


def prefix_cache_eligible(engine) -> bool:
    """Would ``prefix_cache=True`` actually share on this engine?  The flag
    is accepted everywhere but structurally inert off the fully-paged tier
    (DESIGN.md §7).  Delegates to ``engine.capabilities()`` — the one
    source of truth launchers print reasons from."""
    from repro.serve.config import capabilities

    return bool(capabilities(engine)["prefix_cache"])


def _sample_seed(req_index: int, step: int) -> int:
    """PRNG stream id for the ``step``-th token of request ``req_index``.
    Keyed by request identity, not slot, so placement (and preemption
    restarts) can't change samples.  The decode step recomputes this
    on-device as ``seed0 + pos`` (seed0 is written at admission), so keep it
    affine in ``step``.  The request index wraps at 2048 to stay inside
    int32 (2047·1e6 + step < 2^31): streams only repeat between requests
    2048 apart under the same base seed."""
    return (req_index % 2048) * 1_000_003 + step


def latency_stats(completions: Sequence[Completion]) -> Dict[str, Dict[str, float]]:
    """Per-request latency percentiles, in decode-step units (cancelled
    requests are excluded — their streams never ran to a latency).

    queue_steps     — steps spent waiting for a slot (admitted - arrival;
                      a preempted request counts its restart wait too);
    ttft_steps      — steps from arrival until the first token exists
                      (``first_token_step - arrival + 1``: the admission
                      prefill samples it, hence queue + 1 for one-shot
                      admission; a chunked prefill's first token lands at
                      its FINAL chunk, so long prompts honestly show their
                      spread-out admission here);
    tokens_per_step — emitted tokens over the steps the slot was occupied;
    accepted_per_step — speculative decoding only (DESIGN.md §8): tokens
                      committed per draft/verify round for this request
                      (accepted drafts + the verify's correction/bonus
                      token, so the vanilla decode rate is 1.0).
    """
    completions = [c for c in completions if c.finish_reason != "cancelled"]
    if not completions:
        return {}
    queue = np.asarray([c.admitted_step - c.arrival for c in completions], np.float64)
    first = np.asarray(
        [c.first_token_step if c.first_token_step >= 0 else c.admitted_step for c in completions],
        np.float64,
    )
    ttft = first - np.asarray([c.arrival for c in completions], np.float64) + 1.0
    tps = np.asarray(
        [len(c.tokens) / max(1, c.finished_step - c.admitted_step + 1) for c in completions],
        np.float64,
    )

    def pct(a):
        return {
            "p50": float(np.percentile(a, 50)),
            "p99": float(np.percentile(a, 99)),
            "mean": float(np.mean(a)),
        }

    out = {"queue_steps": pct(queue), "ttft_steps": pct(ttft), "tokens_per_step": pct(tps)}
    spec = [c.spec_tokens / c.spec_steps for c in completions if c.spec_steps > 0]
    if spec:
        out["accepted_per_step"] = pct(np.asarray(spec, np.float64))
    return out


class Scheduler:
    """Continuous-batching loop over a ``ServeEngine`` (see module docstring).

    Built from one ``serve.ServeConfig`` — ``Scheduler(engine, config)``.
    The legacy keyword form ``Scheduler(engine, n_slots, temperature=...)``
    still works but emits a ``DeprecationWarning``.

    All jitted calls go through ``engine._with_backend`` so the packed
    dispatch inside the shared decode trace always sees the backend the
    engine was pinned to at construction (DESIGN.md §4).

    ``block_size``: tokens per KV block.  ``n_blocks``: pool capacity in
    blocks (default: dense-equivalent, n_slots × ceil(max_len/block), so the
    classic ``generate`` wrapper can never be preempted); at least
    ceil(max_len/block) so a lone request can always run to completion."""

    def __init__(self, engine, config: Optional[ServeConfig] = None, **legacy):
        if isinstance(config, int):  # legacy positional n_slots
            legacy["n_slots"] = config
            config = None
        if legacy:
            if config is not None:
                raise TypeError("pass either a ServeConfig or legacy keyword args, not both")
            warnings.warn(
                "Scheduler(engine, n_slots, **kwargs) is deprecated; pass "
                "Scheduler(engine, serve.ServeConfig(...))",
                DeprecationWarning,
                stacklevel=2,
            )
            config = ServeConfig(**legacy)
        config = (config or ServeConfig()).resolve(engine)
        self.config = config
        self.eng = engine
        self.cfg = cfg = engine.cfg
        self.n_slots = S = int(config.n_slots)
        self.temperature = float(config.temperature)
        self.top_k = int(config.top_k)
        self._base_key = jax.random.PRNGKey(config.seed)
        self._temp = jnp.float32(max(self.temperature, 1e-6))
        self._offset = cfg.prefix_len if cfg.family == "vlm" else 0
        self._groups = scan_groups(cfg)
        # all traces live on the engine (shared across Scheduler instances —
        # a per-scheduler jit cache would recompile on every serve() call)
        self._fns = engine.scheduler_fns(greedy=self.temperature <= 0.0, top_k=self.top_k)
        self._compiles0 = self._fns.admit_compiles
        # telemetry core (DESIGN.md §13): the registry is always on; span
        # tracing and the profiler window are opt-in knobs.  Created FIRST so
        # every subsystem built below (prefix cache, pool gauges, stats view)
        # can report into the same registry.
        tele = config.telemetry
        self.registry = MetricsRegistry()
        self.tracer = StepTracer(tele.trace_capacity) if tele.trace else NULL_TRACER
        self._profile = make_profile_window(tele.profile_dir, tele.profile_steps)
        self.monitor = StepTimeMonitor()
        self._straggler_warned = False

        self.block_size = blk = int(config.block_size)
        self.max_blocks = -(-engine.max_len // blk)
        self.n_blocks = int(config.n_blocks) or S * self.max_blocks
        if self.n_blocks < self.max_blocks:
            raise ValueError(
                f"n_blocks={self.n_blocks} cannot hold one max_len={engine.max_len} "
                f"request ({self.max_blocks} blocks of {blk})"
            )
        self.pool = BlockPool(self.n_blocks, blk)
        # physical block ids = logical + 1; row 0 of every pool leaf is the
        # trash block evicted slots write into (their table rows are zeroed)
        # (replicated on a mesh — the single-row .at[] edits stay identical
        # on every device, DESIGN.md §12)
        self._block_tables = self._replicate(jnp.zeros((S, self.max_blocks), jnp.int32))

        caps = engine.capabilities()
        # per-block quantized pools (DESIGN.md §11): on the fully-paged tier
        # EVERY admission routes through the §7 tail-prefill trace (start=0
        # on a miss), so miss logits come from the same quantized-pool
        # attention that hits and chunks run — the pool is its own oracle
        # and hit/miss streams stay bit-identical
        self._quant_admit = bool(engine.kv_quant_bits) and bool(caps["fully_paged"])
        # prefix cache (DESIGN.md §7): only the fully-paged tier can share —
        # every cache leaf of every group must live in the block pool, which
        # holds exactly for all-attention decoders (no MoE capacity coupling,
        # no MLA absorbed state quirks).  Elsewhere the flag is accepted and
        # the cache is structurally inert.
        self.prefix: Optional[PrefixCache] = None
        if config.prefix_cache and not self._offset and caps["prefix_cache"]:
            self.prefix = PrefixCache(
                self.pool, blk, engine.params_fingerprint(), registry=self.registry
            )
            self.pool.set_reclaimer(self.prefix.reclaim)
        # chunked prefill (DESIGN.md §10) rides the §7 tail-prefill trace, so
        # it shares the tier test; inert elsewhere like the prefix cache
        self.chunk = (
            int(config.prefill_chunk)
            if config.prefill_chunk and not self._offset and caps["chunked_prefill"]
            else 0
        )
        self.caches = self._init_caches()
        # slot-table state lives ON DEVICE: the per-step loop feeds the
        # previous step's device handles straight back and only downloads
        # the sampled tokens (EOS/budget bookkeeping); admission/eviction
        # touch single rows via .at[slot].set
        self._tokens = self._replicate(jnp.zeros((S,), jnp.int32))
        self._pos = self._replicate(jnp.zeros((S,), jnp.int32))
        self._active = self._replicate(jnp.zeros((S,), bool))
        self._seed0 = self._replicate(jnp.zeros((S,), jnp.int32))
        self._slots: List[Optional[_Slot]] = [None] * S
        self._n_live = 0
        self._queue: collections.deque = collections.deque()
        self._n_submitted = 0
        self._completions: Dict[int, Completion] = {}
        self._on_token: Dict[int, Callable[[int, int], None]] = {}
        self._on_finish: Dict[int, Callable[[Completion], None]] = {}
        self._streamed: Dict[int, int] = {}  # req idx -> tokens already streamed
        self.step_count = 0
        self._buckets_used: set = set()
        # stats is a THIN VIEW over registry counters (StatsView): the dict
        # shape every existing test/bench/launcher reads is unchanged, but
        # serve_<key> counters now live in the registry alongside the gauges
        # and histograms below — one snapshot answers everything
        self.stats = StatsView(self.registry, "serve_")
        for key in (
            "decode_steps",
            "idle_steps",
            "prefill_only_steps",
            "prefills",
            "prefill_chunks",
            "chunked_admissions",
            "admissions",
            "evictions",
            "preemptions",
            "cancellations",
            "tokens_emitted",
            "admission_traces",
            "admission_trace_compiles",
            "chunk_trace_compiles",
            "decode_trace_compiles",
            "peak_live_slots",
            "prefix_hits",
            "prefix_misses",
            "prefix_hit_tokens",
            "prefix_cow_copies",
            "prefix_evicted_blocks",
        ):
            self.stats[key] = 0
        self._decode_cache0 = self._fns.decode_cache_size()
        self._prefix_compiles0 = self._fns.prefix_compiles
        reg = self.registry
        self._h_queue = reg.histogram(
            "serve_queue_wait_steps",
            "steps a finished request waited for a slot (restart wait included)",
            log_buckets(1, 4096),
        )
        self._h_ttft = reg.histogram(
            "serve_ttft_steps",
            "arrival to first sampled token, in decode steps",
            log_buckets(1, 4096),
        )
        self._h_itl = reg.histogram(
            "serve_itl_seconds",
            "wall time per committed token (per-row view of decode-step time)",
            log_buckets(1e-5, 32.0, 4.0),
        )
        self._h_accept = reg.histogram(
            "serve_accepted_per_step",
            "tokens committed per (row, speculative round); vanilla decode is 1",
            log_buckets(1, 16),
        )
        self._g_live = reg.gauge("serve_live_slots", "occupied decode slots")
        self._g_queue = reg.gauge("serve_queue_depth", "requests waiting for admission")
        self._g_pool_live = reg.gauge("serve_pool_live_blocks", "pool blocks held by live requests")
        self._g_pool_free = reg.gauge("serve_pool_free_blocks", "immediately allocatable blocks")
        self._g_pool_cached = reg.gauge(
            "serve_pool_cached_free_blocks", "cached-free tier (prefix blocks reclaimable by LRU)"
        )
        self._g_ewma = reg.gauge("serve_step_time_ewma_seconds", "EWMA decode-step wall time")
        self._g_straggler = reg.gauge(
            "serve_straggler_fraction", "fraction of decode steps flagged slow by the monitor"
        )
        self._timelines: Dict[int, List[Tuple[str, int]]] = {}
        # events and the span tracer share trace_capacity and its
        # oldest-first drop rule (see RingLog)
        self.events: List[Tuple[int, str, int, int]] = RingLog(
            tele.trace_capacity
        )  # (step, kind, req, slot); oldest dropped past trace_capacity
        reg.gauge("serve_pool_bytes", "resident KV pool bytes (all devices)").set(
            self.cache_bytes()
        )
        from repro.serve.sharding import pool_bytes_per_device

        _, per_dev = pool_bytes_per_device(self.eng, blk, self.n_blocks)
        reg.gauge(
            "serve_pool_bytes_per_device",
            "per-device resident paged-pool bytes (head-sharded data leaves divided; §12)",
        ).set(per_dev)
        self._sync_gauges()

    # ------------------------------------------------------------------
    # cache pool
    # ------------------------------------------------------------------
    def _replicate(self, x):
        """Pin host bookkeeping arrays replicated on the engine's mesh (a
        no-op off-mesh): slot state and block tables are edited one row at a
        time on the host path, and an explicit replicated placement keeps
        those edits out of GSPMD's layout search."""
        mesh = getattr(self.eng, "mesh", None)
        if mesh is None:
            return x
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec()))

    def _shard_pool(self, pool):
        """Apply the §12 placement to a freshly-built cache pool: paged DATA
        leaves shard their KV-head axis over the mesh's ``model`` mapping
        (``serve.sharding.pool_pspec`` — replicated when heads don't
        divide), while ``_scale`` exponent siblings and every non-paged
        per-row leaf replicate."""
        mesh, rules = getattr(self.eng, "mesh", None), getattr(self.eng, "rules", None)
        if mesh is None or rules is None:
            return pool
        from repro.serve.sharding import pool_pspec

        for g in self._groups:
            axis = 1 if g.stacked else 0
            for j in range(len(g.unit)):
                sub = pool[g.name][f"sub{j}"]
                for name, leaf in sub.items():
                    if g.paged[j] and name in PAGED_CACHE_LEAVES:
                        spec = pool_pspec(rules, leaf.shape, axis)
                    else:
                        spec = PartitionSpec()
                    sub[name] = jax.device_put(leaf, NamedSharding(mesh, spec))
        return pool

    def _init_caches(self):
        """Zero cache pool with exactly the prefill trace's leaf dtypes.
        Paged leaves (GroupSpec.paged ∩ PAGED_CACHE_LEAVES) become shared
        pools of n_blocks+1 blocks (``paged_pool_shape``: (n, K, block, hd)
        head-major KV, (n, block, r) MLA) — +1 for the trash block — replacing
        the per-slot max_len rows entirely; everything else keeps its
        per-row layout with the batch axis widened from 1 to n_slots.

        With ``engine.kv_quant_bits`` set (DESIGN.md §11) the paged data
        pools hold int8 mantissa words (last dim halved at 4 bits — two
        lanes per word) and each gains an int32 ``<name>_scale`` sibling of
        one exponent per (physical block[, KV head])."""
        shapes = self.eng.prefill_cache_shapes()
        S, blk = self.n_slots, self.block_size
        n_phys = self.n_blocks + 1
        qbits = self.eng.kv_quant_bits
        pool = {}
        for g in self._groups:
            axis = 1 if g.stacked else 0
            sub_pool = {}
            for j in range(len(g.unit)):
                sub = {}
                for name, sd in shapes[g.name][f"sub{j}"].items():
                    if g.paged[j] and name in PAGED_CACHE_LEAVES:
                        lead, feat = sd.shape[:axis], sd.shape[axis + 2 :]
                        if qbits:
                            if qbits == 4:
                                feat = feat[:-1] + (feat[-1] // 2,)
                            sub[name] = jnp.zeros(
                                paged_pool_shape(lead, n_phys, blk, feat), jnp.int8
                            )
                            sub[name + "_scale"] = jnp.zeros(
                                lead + (n_phys,) + feat[:-1], jnp.int32
                            )
                            continue
                        shape = paged_pool_shape(lead, n_phys, blk, feat)
                    else:
                        shape = sd.shape[:axis] + (S,) + sd.shape[axis + 1 :]
                    sub[name] = jnp.zeros(shape, sd.dtype)
                sub_pool[f"sub{j}"] = sub
            pool[g.name] = sub_pool
        return self._shard_pool(pool)

    def cache_bytes(self) -> int:
        """Resident KV bytes of the pool (the §6 capacity-math numerator)."""
        return sum(
            leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(self.caches)
        )

    # ------------------------------------------------------------------
    # queue / admission
    # ------------------------------------------------------------------
    def submit(
        self,
        req: Request,
        *,
        on_token: Optional[Callable[[int, int], None]] = None,
        on_finish: Optional[Callable[[Completion], None]] = None,
    ) -> int:
        """Enqueue a request; returns its index (completion order key).

        ``on_token(index, token)`` streams each committed token (overrides
        ``ServeConfig.on_token``); ``on_finish(completion)`` fires once,
        after the last token, for any finish reason including cancellation.
        Preemption replays are deduplicated — every token streams once."""
        prompt = np.asarray(req.tokens, np.int32).reshape(-1)
        budget = min(int(req.max_new_tokens), self.eng.max_len - self._offset - prompt.shape[0] + 1)
        if budget < 1:
            raise ValueError(
                f"prompt of length {prompt.shape[0]} leaves no room for "
                f"generation under max_len={self.eng.max_len}"
            )
        idx = self._n_submitted
        self._n_submitted += 1
        cb = on_token if on_token is not None else self.config.on_token
        if cb is not None:
            self._on_token[idx] = cb
        if on_finish is not None:
            self._on_finish[idx] = on_finish
        self._timelines[idx] = [("submit", self.step_count)]
        self._queue.append((idx, prompt, budget, req))
        return idx

    def cancel(self, idx: int) -> bool:
        """Cancel request ``idx``: a queued request is dropped; a live one is
        torn down immediately — its blocks return to the pool NOW (the
        zeroed table row redirects any in-flight writes to the trash block,
        so surviving rows never notice) and its partial output becomes a
        ``finish_reason='cancelled'`` Completion.  Returns False when the
        request is unknown or already finished."""
        for i, item in enumerate(self._queue):
            if item[0] == idx:
                del self._queue[i]
                tl = self._timelines.get(idx)
                if tl is not None:
                    tl.append(("cancel", self.step_count))
                self.tracer.instant("cancel", req=idx)
                self._seal(
                    Completion(
                        index=idx,
                        tokens=[],
                        prompt_len=int(item[1].shape[0]),
                        finish_reason="cancelled",
                        slot=-1,
                        arrival=item[3].arrival,
                        admitted_step=-1,
                        finished_step=self.step_count,
                    )
                )
                self.events.append((self.step_count, "cancel", idx, -1))
                self.stats["cancellations"] += 1
                return True
        for slot, state in enumerate(self._slots):
            if state is not None and state.index == idx:
                self._emit_tokens(state)
                self._release(slot)
                tl = self._timelines.get(idx)
                if tl is not None:
                    tl.append(("cancel", self.step_count))
                self.tracer.instant("cancel", req=idx, slot=slot)
                self._seal(
                    Completion(
                        index=idx,
                        tokens=list(state.out),
                        prompt_len=state.prompt_len,
                        finish_reason="cancelled",
                        slot=slot,
                        arrival=state.req.arrival,
                        admitted_step=state.admitted_step,
                        finished_step=self.step_count,
                        first_token_step=state.first_token_step,
                    )
                )
                self.events.append((self.step_count, "cancel", idx, slot))
                self.stats["cancellations"] += 1
                return True
        return False

    def _seal(self, comp: Completion) -> None:
        """Record a completion, attach its lifecycle timeline, and fire its
        on_finish callback."""
        comp.timeline = self._timelines.pop(comp.index, [])
        self._completions[comp.index] = comp
        cb = self._on_finish.get(comp.index)
        if cb is not None:
            cb(comp)

    def _emit_tokens(self, state: _Slot) -> None:
        """Stream any not-yet-streamed committed tokens of this request and
        record one 'token' timeline entry per delivery.  Dedup is by COUNT
        against the request's lifetime stream: preemption replays are
        token-exact, so a replayed prefix is exactly what was already
        delivered — streamed once, one timeline entry."""
        n = self._streamed.get(state.index, 0)
        if len(state.out) <= n:
            return
        cb = self._on_token.get(state.index)
        tl = self._timelines.get(state.index)
        for t in state.out[n:]:
            if cb is not None:
                cb(state.index, int(t))
            if tl is not None:
                tl.append(("token", self.step_count))
        self._streamed[state.index] = len(state.out)

    def _bucket(self, lp: int) -> int:
        """Power-of-two padded prompt length, capped at the cache room."""
        b = 1
        while b < lp:
            b <<= 1
        return min(b, self.eng.max_len - self._offset)

    def _pop_due(self):
        """Highest-priority due request, FIFO among equal priorities (a
        future-dated or low-priority head must not block due work behind
        it).  ``priority=0`` everywhere reduces to plain FIFO-among-due."""
        best = None
        for i, item in enumerate(self._queue):
            if item[3].arrival <= self.step_count:
                if best is None or item[3].priority > self._queue[best][3].priority:
                    best = i
        if best is None:
            return None
        item = self._queue[best]
        del self._queue[best]
        return item

    def _match_prefix(self, prompt: np.ndarray, req: Request) -> Tuple[int, List[int]]:
        """Cached-prefix match for admission: ``(matched, path_bids)`` where
        ``path_bids`` cover the first ceil(matched/block) prompt blocks.
        Capped at ``lp - 1`` so a hit always leaves >= 1 tail token to
        prefill (the admission must sample a first token)."""
        if self.prefix is None or req.extras:
            return 0, []
        return self.prefix.match(
            prompt, self.eng.params_fingerprint(), max_match=prompt.shape[0] - 1
        )

    def _admit(self) -> None:
        for slot in range(self.n_slots):
            if self._slots[slot] is not None:
                continue
            item = self._pop_due()
            if item is None:
                return
            idx, prompt, budget, req = item
            lp = prompt.shape[0]
            # +1 covers the first decode write at pos = offset+lp; clamp to
            # the table width — a FULL-length prompt (offset+lp == max_len, a
            # block multiple) has budget 1 and never decodes, so that extra
            # block doesn't exist and mustn't be demanded
            need = min((self._offset + lp) // self.block_size + 1, self.max_blocks)
            matched, path = self._match_prefix(prompt, req)
            m_full, m_part = divmod(matched, self.block_size)
            # pin the matched path FIRST: alloc's cached-free reclaim (LRU
            # trie eviction) must not recycle the very blocks we matched
            shared, src = path[:m_full], (path[m_full] if m_part else None)
            for bid in shared:
                self.pool.acquire(bid)
            if src is not None:
                self.pool.acquire(src)
            fresh = self.pool.alloc(need - m_full)
            if fresh is None:
                # memory-bound: undo the pins, put the request back at ITS
                # queue position (front among due) and stop — admitting a
                # smaller later request instead would starve large prompts
                for bid in shared:
                    self.pool.free(bid)
                if src is not None:
                    self.pool.free(src)
                self._queue.appendleft(item)
                return
            if src is not None:
                # copy-on-write: the hit ends INSIDE a cached block — clone
                # its physical row so this request can append into a private
                # copy while the source keeps serving the cache
                self.caches = self.eng._with_backend(
                    self._fns.cow_copy, self.caches, jnp.int32(src + 1), jnp.int32(fresh[0] + 1)
                )
                self.pool.free(src)
                self.stats["prefix_cow_copies"] += 1
                self.tracer.instant("cow", req=idx, src=src, dst=fresh[0])
            if matched:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_hit_tokens"] += matched
                self.tracer.instant("prefix_hit", req=idx, tokens=matched)
            elif self.prefix is not None and not req.extras:
                self.stats["prefix_misses"] += 1
            self._admit_one(slot, idx, prompt, budget, req, shared + fresh, start=matched)

    def _admit_batch(self, prompt: np.ndarray, req: Request):
        """Bucketed admission inputs for the MISS path: (bucket, batch) with
        the prompt right-padded to its power-of-two bucket and any request
        extras (encdec frames / vlm patches) attached.  Shared with the
        speculative scheduler's draft-pool mirror so the two prefills can
        never diverge in prep."""
        lp = prompt.shape[0]
        bucket = self._bucket(lp)
        padded = np.zeros(bucket, np.int32)
        padded[:lp] = prompt
        batch = {"tokens": jnp.asarray(padded[None])}
        if req.extras:
            batch.update({k: jnp.asarray(v) for k, v in req.extras.items()})
        return bucket, batch

    def _new_slot(
        self, slot: int, idx: int, prompt: np.ndarray, budget: int, req: Request, blocks: List[int]
    ) -> _Slot:
        """Host-side slot bookkeeping shared by one-shot and chunked
        admission — the device row stays untouched here."""
        state = _Slot(
            index=idx,
            eos_id=int(req.eos_id),
            budget=budget,
            prompt=prompt,
            req=req,
            out=[],
            admitted_step=self.step_count,
            pos=self._offset + prompt.shape[0],
            blocks=blocks,
        )
        self._slots[slot] = state
        self._n_live += 1
        self.stats["peak_live_slots"] = max(self.stats["peak_live_slots"], self._n_live)
        tl = self._timelines.get(idx)
        if tl is not None:
            tl.append(("admit", self.step_count))
        return state

    def _admit_one(
        self,
        slot: int,
        idx: int,
        prompt: np.ndarray,
        budget: int,
        req: Request,
        blocks: List[int],
        start: int = 0,
    ) -> None:
        lp = prompt.shape[0]
        if self.chunk and not req.extras and (lp - start) > self.chunk:
            # chunked admission (DESIGN.md §10): hold the blocks, keep the
            # DEVICE table row zeroed (a populated row would let the shared
            # decode dispatch write through it mid-prefill), and let the
            # step loop run one tail-prefill chunk per step
            row = np.zeros(self.max_blocks, np.int32)
            row[: len(blocks)] = np.asarray(blocks, np.int32) + 1  # physical ids
            state = self._new_slot(slot, idx, prompt, budget, req, blocks)
            state.prefilling = True
            state.done = start
            state.row = row
            self.stats["chunked_admissions"] += 1
            self.events.append((self.step_count, "admit", idx, slot))
            return
        # prefix hit: prefill only the uncached tail, traced start offset.
        # Quantized pools route MISSES (start=0) through the same trace so
        # the first sampled token always comes from quantized-pool
        # attention — dense-prefill logits would split hit/miss numerics
        # (DESIGN.md §11).
        tail_prefill = bool(start) or (self._quant_admit and not req.extras)
        tail = lp - start
        bucket = self._bucket(tail if tail_prefill else lp)
        # the span closes in _activate, once the first token is on the host
        tr = self.tracer
        span = (
            tr.span(
                "admit", step=self.step_count, req=idx, slot=slot, prompt=lp, bucket=bucket,
                start=start,
            )
            if tr.enabled
            else NULL_SPAN
        )
        span.__enter__()
        row = np.zeros(self.max_blocks, np.int32)
        row[: len(blocks)] = np.asarray(blocks, np.int32) + 1  # physical ids
        self._block_tables = self._block_tables.at[slot].set(jnp.asarray(row))
        if tail_prefill:
            padded = np.zeros(bucket, np.int32)
            padded[:tail] = prompt[start:]
            admit = self._fns.admit_prefix_step(bucket, self.block_size)
            first_t, self.caches = self.eng._with_backend(
                admit,
                self.eng.params,
                {"tokens": jnp.asarray(padded[None])},
                jnp.int32(tail),
                jnp.int32(start),
                self.caches,
                self._block_tables[slot],
                jnp.int32(_sample_seed(idx, 0)),
                self._base_key,
                self._temp,
            )
            self._buckets_used.add(("prefix", bucket, self.block_size))
        else:
            bucket, batch = self._admit_batch(prompt, req)
            admit = self._fns.admit_step(bucket, self.block_size)
            first_t, self.caches = self.eng._with_backend(
                admit,
                self.eng.params,
                batch,
                jnp.int32(lp),
                self.caches,
                self._block_tables[slot],
                jnp.int32(slot),
                jnp.int32(_sample_seed(idx, 0)),
                self._base_key,
                self._temp,
            )
            self._buckets_used.add((bucket, self.block_size))
        self.stats["prefills"] += 1
        # admission_traces: distinct bucketed trace shapes THIS run admitted
        # through (each compiled at most once, engine-memoized across runs);
        # admission_trace_compiles: traces actually built fresh for this run
        # (0 on a warm engine); chunk_trace_compiles the tail/chunk subset
        self.stats["admission_traces"] = len(self._buckets_used)
        self.stats["admission_trace_compiles"] = self._fns.admit_compiles - self._compiles0
        self.stats["chunk_trace_compiles"] = self._fns.prefix_compiles - self._prefix_compiles0
        if self.prefix is not None and not req.extras:
            # index every prompt block (shared levels dedupe onto existing
            # nodes) while the blocks are still pinned by this table
            self.prefix.insert(prompt, blocks, self.eng.params_fingerprint())
            self.stats["prefix_evicted_blocks"] = self.prefix.stats["evicted_blocks"]
        self._register(slot, idx, prompt, budget, req, blocks, first_t, span)

    def _prefill_chunk(self, slot: int) -> None:
        """Run ONE tail-prefill chunk for a prefilling slot — the §7 traced-
        start-offset trace with ``start = tokens done``, so the pool after
        the final chunk is bit-identical to a one-shot prefill.  Non-final
        chunks discard their sampled token (junk past the real tail); the
        final chunk samples the request's first token with the SAME
        (request, step=0) seed one-shot admission uses, then publishes the
        device table row and activates the slot.  A non-final chunk's span
        covers its dispatch only (nothing waits for it; its device time
        lands in the next span that syncs); the final chunk's closes in
        ``_activate``, once the first token is on the host."""
        state = self._slots[slot]
        lp = state.prompt_len
        tail = min(self.chunk, lp - state.done)
        final = state.done + tail == lp
        bucket = self._bucket(tail)
        padded = np.zeros(bucket, np.int32)
        padded[:tail] = state.prompt[state.done : state.done + tail]
        admit = self._fns.admit_prefix_step(bucket, self.block_size)
        tr = self.tracer
        span = (
            tr.span(
                "chunk", step=self.step_count, req=state.index, slot=slot, done=state.done,
                tail=tail, bucket=bucket,
            )
            if tr.enabled
            else NULL_SPAN
        )
        span.__enter__()
        first_t, self.caches = self.eng._with_backend(
            admit,
            self.eng.params,
            {"tokens": jnp.asarray(padded[None])},
            jnp.int32(tail),
            jnp.int32(state.done),
            self.caches,
            jnp.asarray(state.row),  # device row stays zeroed until final
            jnp.int32(_sample_seed(state.index, 0)),
            self._base_key,
            self._temp,
        )
        if not final:
            span.__exit__(None, None, None)
        self._buckets_used.add(("prefix", bucket, self.block_size))
        state.done += tail
        tl = self._timelines.get(state.index)
        if tl is not None:
            tl.append(("chunk", self.step_count))
        self.stats["prefill_chunks"] += 1
        self.stats["admission_traces"] = len(self._buckets_used)
        self.stats["admission_trace_compiles"] = self._fns.admit_compiles - self._compiles0
        self.stats["chunk_trace_compiles"] = self._fns.prefix_compiles - self._prefix_compiles0
        if not final:
            return
        self.stats["prefills"] += 1
        self._block_tables = self._block_tables.at[slot].set(jnp.asarray(state.row))
        if self.prefix is not None and not state.req.extras:
            # only now do the blocks hold the full prompt's KV — inserting
            # earlier would expose half-prefilled blocks to other admissions
            self.prefix.insert(state.prompt, state.blocks, self.eng.params_fingerprint())
            self.stats["prefix_evicted_blocks"] = self.prefix.stats["evicted_blocks"]
        self._activate(slot, first_t, span)

    def _advance_prefills(self) -> None:
        """The mixed-batch chunk pass: one prefill chunk per prefilling slot
        per step, alongside (before) the live decode dispatch."""
        for slot in range(self.n_slots):
            state = self._slots[slot]
            if state is not None and state.prefilling:
                self._prefill_chunk(slot)

    def _register(
        self,
        slot: int,
        idx: int,
        prompt: np.ndarray,
        budget: int,
        req: Request,
        blocks: List[int],
        first_t,
        span,
    ) -> None:
        """Slot bookkeeping after the fused one-shot admission dispatch."""
        self._new_slot(slot, idx, prompt, budget, req, blocks)
        self.events.append((self.step_count, "admit", idx, slot))
        self._activate(slot, first_t, span)

    def _activate(self, slot: int, first_t, span) -> None:
        """Flip a slot live once the full prompt's KV is resident and its
        first token is sampled: publish the device slot-table row state the
        decode dispatch reads, record the first token, and apply the
        instant finish checks (budget-1 / immediate EOS).  ``span``, the
        admission's, closes once the first token is on the host."""
        state = self._slots[slot]
        first = int(np.asarray(first_t))
        span.__exit__(None, None, None)
        state.prefilling = False
        state.out.append(first)
        state.first_token_step = self.step_count
        self.stats["admissions"] += 1
        self.stats["tokens_emitted"] += 1
        self._tokens = self._tokens.at[slot].set(first_t)
        self._pos = self._pos.at[slot].set(state.pos)
        self._active = self._active.at[slot].set(True)
        # seed0 + pos == _sample_seed(idx, len(out)) at every future step
        self._seed0 = self._seed0.at[slot].set(_sample_seed(state.index, 1) - state.pos)
        self._emit_tokens(state)
        if first == state.eos_id or len(state.out) >= state.budget:
            self._finish(slot, "eos" if first == state.eos_id else "length")

    # ------------------------------------------------------------------
    # eviction / preemption
    # ------------------------------------------------------------------
    def _release(self, slot: int) -> _Slot:
        """Common teardown: free blocks, zero the table row (all writes of
        this row now land in the trash block), deactivate."""
        state = self._slots[slot]
        self.pool.free_all(state.blocks)
        self._block_tables = self._block_tables.at[slot].set(0)
        self._slots[slot] = None
        self._n_live -= 1
        self._active = self._active.at[slot].set(False)
        return state

    def _finish(self, slot: int, reason: str) -> None:
        state = self._release(slot)
        self._h_queue.observe(max(0, state.admitted_step - state.req.arrival))
        if state.first_token_step >= 0:
            self._h_ttft.observe(state.first_token_step - state.req.arrival + 1)
        self.tracer.instant("evict", req=state.index, slot=slot, reason=reason)
        tl = self._timelines.get(state.index)
        if tl is not None:
            tl.append(("finish", self.step_count))
        self._seal(
            Completion(
                index=state.index,
                tokens=list(state.out),
                prompt_len=state.prompt_len,
                finish_reason=reason,
                slot=slot,
                arrival=state.req.arrival,
                admitted_step=state.admitted_step,
                finished_step=self.step_count,
                first_token_step=state.first_token_step,
            )
        )
        self.events.append((self.step_count, "evict", state.index, slot))
        self.stats["evictions"] += 1

    def _preempt(self, slot: int) -> None:
        """Evict a live request under pool pressure and requeue it at the
        front for a from-scratch restart (deterministic / (request,step)-
        keyed sampling makes the replay token-identical; already-streamed
        tokens are not re-delivered — ``_emit_tokens`` dedupes)."""
        state = self._release(slot)
        self._queue.appendleft((state.index, state.prompt, state.budget, state.req))
        self.events.append((self.step_count, "preempt", state.index, slot))
        self.stats["preemptions"] += 1
        self.tracer.instant("preempt", req=state.index, slot=slot)
        tl = self._timelines.get(state.index)
        if tl is not None:
            tl.append(("preempt", self.step_count))

    def _grow_tables(self, horizon: int = 0) -> None:
        """Allocate blocks for every live row through position
        ``pos + horizon`` (clamped to the cache end), oldest request first;
        exhaustion preempts the LOWEST-PRIORITY live request, youngest
        among ties (vLLM policy: the oldest high-priority request always
        progresses, so the loop terminates).  The vanilla decode step needs
        ``horizon=0`` (one write at ``pos``); the speculative controller
        reserves its whole draft window up front so a verify trace never
        writes through a missing table entry."""
        tr = self.tracer
        if not tr.enabled:
            self._grow_rows(horizon)
            return
        allocs0 = self.pool.total_allocs
        with tr.span("grow", step=self.step_count) as span:
            self._grow_rows(horizon)
            span.args["new_blocks"] = self.pool.total_allocs - allocs0

    def _grow_rows(self, horizon: int) -> None:
        order = sorted(
            (s for s in range(self.n_slots) if self._slots[s] is not None),
            key=lambda s: (self._slots[s].admitted_step, self._slots[s].index),
        )
        for slot in order:
            state = self._slots[slot]
            if state is None:  # preempted by an older slot's growth
                continue
            need_bi = min(state.pos + horizon, self.eng.max_len - 1) // self.block_size
            while state is not None and need_bi >= len(state.blocks):
                bi = len(state.blocks)
                got = self.pool.alloc(1)
                if got is not None:
                    state.blocks.append(got[0])
                    self._block_tables = self._block_tables.at[slot, bi].set(got[0] + 1)
                    continue
                victim = max(
                    (s for s in range(self.n_slots) if self._slots[s] is not None),
                    key=lambda s: (
                        -self._slots[s].req.priority,
                        self._slots[s].admitted_step,
                        self._slots[s].index,
                    ),
                )
                self._preempt(victim)
                if victim == slot:
                    state = None  # the requester itself was the victim; it restarts

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------
    def _n_decoding(self) -> int:
        """Live slots past their prefill (the decode dispatch's real rows)."""
        return sum(1 for st in self._slots if st is not None and not st.prefilling)

    def _step_span(self):
        """The ``step`` span (``NULL_SPAN`` with tracing off); its counters
        are filled in by ``_count_step``."""
        tr = self.tracer
        return tr.span("step", step=self.step_count) if tr.enabled else NULL_SPAN

    def _count_step(self, span) -> None:
        """The step span's counters, read once the step's growth,
        admissions and chunks are in: the rows its decode runs over, and
        the pool at its fullest within the step."""
        if self.tracer.enabled:
            span.args.update(
                live=self._n_live,
                decoding=self._n_decoding(),
                queued=len(self._queue),
                blocks_live=self.pool.n_live,
            )

    @contextlib.contextmanager
    def _emit_span(self, step: int):
        """The ``emit`` span around a step's post-dispatch loop, with the
        tokens it emitted and the requests it finished."""
        tr = self.tracer
        if not tr.enabled:
            yield
            return
        tokens0, finished0 = self.stats["tokens_emitted"], self.stats["evictions"]
        with tr.span("emit", step=step) as span:
            yield
            span.args["tokens"] = self.stats["tokens_emitted"] - tokens0
            span.args["finished"] = self.stats["evictions"] - finished0

    def _sync_gauges(self) -> None:
        """Refresh the point-in-time occupancy gauges (host ints, per step)."""
        self._g_live.set(self._n_live)
        self._g_queue.set(len(self._queue))
        self._g_pool_live.set(self.pool.n_live)
        self._g_pool_free.set(self.pool.n_free)
        self._g_pool_cached.set(self.pool.n_cached_free)

    def _observe_step_time(self, dt: float) -> None:
        """Feed one decode-step wall time to the straggler monitor, mirror
        its EWMA/straggler-fraction into gauges, and warn ONCE when the
        flagged fraction stays above ``telemetry.straggler_warn`` past
        warmup (one line; the gauges keep tracking either way)."""
        self.monitor.observe(dt)
        self._g_ewma.set(self.monitor.ewma or 0.0)
        frac = self.monitor.straggler_fraction()
        self._g_straggler.set(frac)
        warn = self.config.telemetry.straggler_warn
        if (
            warn
            and not self._straggler_warned
            and self.monitor.count > 2 * self.monitor.warmup
            and frac > warn
        ):
            self._straggler_warned = True
            print(
                f"[serve] sustained stragglers: {frac:.0%} of {self.monitor.count} decode "
                f"steps ran > {self.monitor.threshold:g}x the EWMA step time "
                f"({self.monitor.ewma:.4g}s)",
                file=sys.stderr,
            )

    def step(self) -> bool:
        """Grow live requests' tables, admit what still fits, advance one
        prefill chunk per prefilling slot, run one ragged decode step over
        the active slots.  Growth runs FIRST so live requests reserve their
        next blocks before admission spends them — otherwise a just-admitted
        request could be preempted by an older slot's boundary crossing in
        the same step, wasting its whole admission prefill.  Returns False
        once the queue is drained and every slot is idle."""
        if self._profile is not None:
            self._profile.on_step()
        with self._step_span() as span:
            return self._step(span)

    def _step(self, span) -> bool:
        n = self.step_count
        self._grow_tables()
        self._admit()
        if self.prefix is not None:
            self.stats["prefix_evicted_blocks"] = self.prefix.stats["evicted_blocks"]
        if self._n_live == 0:
            self._count_step(span)
            if not self._queue:
                self._sync_gauges()
                return False
            # all live work done but arrivals are still in the future (or
            # the pool can't fit the next prompt yet): tick time forward
            self.step_count += 1
            self.stats["idle_steps"] += 1
            self._sync_gauges()
            return True

        self._advance_prefills()
        self._count_step(span)
        n_decoding = self._n_decoding()
        if n_decoding == 0:
            # every live slot is mid-prefill (or finished at activation):
            # the chunk pass above was this step's work; time still advances
            self.step_count += 1
            self.stats["prefill_only_steps"] += 1
            self._sync_gauges()
            return bool(self._n_live or self._queue)

        tr = self.tracer
        t0 = time.perf_counter()
        with (
            tr.span("decode", step=n, n_decode=n_decoding, n_live=self._n_live)
            if tr.enabled
            else NULL_SPAN
        ):
            self._tokens, self._pos, self.caches = self.eng._with_backend(
                self._fns.decode_step,
                self.eng.params,
                self.caches,
                self._tokens,
                self._pos,
                self._active,
                self._seed0,
                self._block_tables,
                self._base_key,
                self._temp,
            )
            nxt = np.asarray(self._tokens)  # the loop's one host sync
        dt = time.perf_counter() - t0
        self.step_count += 1
        self.stats["decode_steps"] += 1
        self.stats["decode_trace_compiles"] = self._fns.decode_cache_size() - self._decode_cache0
        self._observe_step_time(dt)

        with self._emit_span(n):
            for s, state in enumerate(self._slots):
                if state is None or state.prefilling:
                    continue
                state.pos += 1  # mirror of the device's pos + active
                tok = int(nxt[s])
                state.out.append(tok)
                self.stats["tokens_emitted"] += 1
                self._h_itl.observe(dt)
                self._emit_tokens(state)
                if tok == state.eos_id:
                    self._finish(s, "eos")
                elif len(state.out) >= state.budget:
                    self._finish(s, "length")
        self._sync_gauges()
        return bool(self._n_live or self._queue)

    def run(self) -> List[Completion]:
        """Drain the queue; completions are returned in submission order."""
        try:
            while self.step():
                pass
        finally:
            if self._profile is not None:
                self._profile.stop()
        return [self._completions[i] for i in sorted(self._completions)]


def serve_requests(
    engine, requests: Sequence[Request], config: Optional[ServeConfig] = None
) -> Tuple[List[Completion], Scheduler]:
    """One-shot helper: schedule ``requests`` onto ``engine`` and drain.
    ``config.speculative`` swaps in the draft/verify controller
    (DESIGN.md §8)."""
    config = (config or ServeConfig()).resolve(engine, requests)
    if config.speculative is not None:
        from repro.serve.speculative import SpeculativeScheduler

        sched = SpeculativeScheduler(engine, config)
    else:
        sched = Scheduler(engine, config)
    for r in requests:
        sched.submit(r)
    return sched.run(), sched
