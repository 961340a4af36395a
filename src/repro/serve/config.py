"""The serving surface's one configuration object (DESIGN.md §10).

``serve()`` had accreted ten keyword arguments plus launcher-only
eligibility warnings; every knob now lives in ``ServeConfig`` — one
validated, frozen dataclass that is the single construction path for the
scheduler (``ServeEngine.serve``, ``serve_requests``, ``Scheduler``,
``AsyncServeEngine`` all take it).  Cross-feature conflicts are rejected
HERE, at construction, instead of deep inside a scheduler subclass:

  * ``prefix_cache`` + ``speculative`` — sharing draft-pool blocks under
    the radix index is designed but not wired (DESIGN.md §8);
  * ``speculative`` + ``prefill_chunk`` — the draft pool mirrors the
    target's admission prefill one-shot; mirroring per chunk is not wired.

``capabilities(engine)`` is the structural-eligibility report the
launcher warnings and the scheduler's inert-flag decisions both read —
one source of truth for the fully-paged tier tests, with human-readable
reasons instead of a bare boolean.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Observability knobs for one scheduler (DESIGN.md §13).

    Metrics (the registry behind ``Scheduler.stats`` plus gauges and
    latency histograms) are ALWAYS on — they are host-side integer
    arithmetic inside an accelerator-bound loop, held ≤ 5 % overhead by
    the gated ``serve_telemetry_overhead`` bench.  The knobs here gate
    the optional layers:

    trace           — record step spans and instants into the ring
                      tracer, each span also a ``jax.profiler``
                      annotation (off: the scheduler holds
                      ``NULL_TRACER``);
    trace_capacity  — ring capacity shared by the tracer AND the
                      scheduler's ``events`` log: both keep the most
                      recent ``trace_capacity`` records and silently drop
                      the oldest beyond that, bounding memory on
                      long-running serves;
    profile_dir     — non-empty arms a ``jax.profiler`` capture window
                      (TensorBoard trace) over the first
                      ``profile_steps`` serve steps; with ``trace`` on,
                      the program's spans appear in it as host events;
    profile_steps   — capture-window length in serve steps;
    straggler_warn  — warn once (one line on stderr) when the step-time
                      monitor's straggler fraction exceeds this after
                      warmup; 0 disables the warning (the gauges stay).
    """

    trace: bool = False
    trace_capacity: int = 4096
    profile_dir: str = ""
    profile_steps: int = 8
    straggler_warn: float = 0.25

    def __post_init__(self):
        if self.trace_capacity < 1:
            raise ValueError(f"trace_capacity must be >= 1, got {self.trace_capacity}")
        if self.profile_steps < 1:
            raise ValueError(f"profile_steps must be >= 1, got {self.profile_steps}")
        if not 0.0 <= self.straggler_warn <= 1.0:
            raise ValueError(
                f"straggler_warn is a fraction in [0, 1] (0 = off), got {self.straggler_warn}"
            )


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Every serving knob in one validated object.

    n_slots        — decode slot-table size; 0 resolves per workload
                     (``resolve``: min(len(requests), 8), or 8 for an
                     open-ended async engine);
    temperature    — sampling temperature (<= 0: greedy);
    top_k          — top-k sampling cutoff (0: off);
    seed           — base PRNG seed for (request, step)-keyed streams;
    block_size     — tokens per paged KV block;
    n_blocks       — pool capacity in blocks (0: dense-equivalent,
                     n_slots x ceil(max_len/block));
    prefix_cache   — radix prefix cache over the pool (DESIGN.md §7;
                     structurally inert off the fully-paged tier);
    speculative    — a ``serve.SpeculativeConfig`` enabling draft-K/
                     verify-K+1 self-speculative decoding (DESIGN.md §8);
    prefill_chunk  — > 0 splits admission prefills into chunks of at most
                     this many tokens, scheduled one per step alongside
                     live decode (DESIGN.md §10; inert off the fully-paged
                     tier).  Token streams are bit-identical to one-shot
                     admission — only latency shape changes;
    on_token       — default per-token streaming callback
                     ``cb(request_index, token)``, fired as each token is
                     committed (per-request overrides via
                     ``Scheduler.submit``); replays after preemption are
                     deduplicated, so every token streams exactly once;
    telemetry      — observability knobs (``TelemetryConfig``): span
                     tracing, ring capacities, profiler window,
                     straggler warning (DESIGN.md §13).
    """

    n_slots: int = 0
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    block_size: int = 16
    n_blocks: int = 0
    prefix_cache: bool = False
    speculative: Optional[Any] = None  # serve.SpeculativeConfig
    prefill_chunk: int = 0
    on_token: Optional[Callable[[int, int], None]] = None
    telemetry: TelemetryConfig = TelemetryConfig()

    def __post_init__(self):
        if not isinstance(self.telemetry, TelemetryConfig):
            raise ValueError(
                f"telemetry must be a TelemetryConfig, got {type(self.telemetry).__name__}"
            )
        if self.n_slots < 0:
            raise ValueError(f"n_slots must be >= 0 (0 = auto), got {self.n_slots}")
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), got {self.top_k}")
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {self.block_size}")
        if self.n_blocks < 0:
            raise ValueError(f"n_blocks must be >= 0 (0 = dense-equivalent), got {self.n_blocks}")
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0 (0 = one-shot), got {self.prefill_chunk}")
        if self.prefix_cache and self.speculative is not None:
            # sharing draft-pool blocks under the radix index is designed
            # but not wired (DESIGN.md §8); refuse loudly over silently
            # dropping one of the two features
            raise ValueError("speculative decoding and prefix_cache are mutually exclusive")
        if self.speculative is not None and self.prefill_chunk:
            raise ValueError(
                "speculative decoding and prefill_chunk are mutually exclusive "
                "(the draft pool mirrors admission prefills one-shot; DESIGN.md §10)"
            )

    def resolve(self, engine=None, requests: Sequence[Any] = ()) -> "ServeConfig":
        """The fully-explicit copy a Scheduler is built from: ``n_slots=0``
        becomes min(len(requests), 8) for a one-shot workload or 8 for an
        open-ended (async) engine — the default that used to hide inside
        ``serve()`` and that benchmarks/tests re-derived inconsistently.
        ``engine`` is accepted for future engine-dependent defaults."""
        n = self.n_slots
        if not n:
            n = max(1, min(len(requests), 8)) if len(requests) else 8
        return dataclasses.replace(self, n_slots=n)


@dataclasses.dataclass(frozen=True)
class Capability:
    """One structural-eligibility verdict: truthy iff supported; ``reason``
    says which architectural property blocks the feature when not."""

    supported: bool
    reason: str = ""

    def __bool__(self) -> bool:
        return self.supported


def _tier_reasons(engine, *, allow_mla: bool) -> list:
    """Why this engine misses the fully-paged tier (empty when it holds).
    Mirrors ``scheduler.fully_paged_tier`` clause for clause so the report
    and the eligibility test can never disagree."""
    from repro.serve.scheduler import fully_paged_tier

    cfg = engine.cfg
    r = []
    if cfg.family != "decoder":
        r.append(f"family '{cfg.family}' is not an all-attention decoder")
    if cfg.moe:
        r.append("MoE capacity competition couples tokens across the batch")
    if cfg.use_mla and not allow_mla:
        r.append("MLA's compressed cache has no tail-prefill trace (DESIGN.md §7)")
    if not r and not fully_paged_tier(engine, allow_mla=allow_mla):
        r.append("non-paged per-row cache state (recurrent/SSD/ring/cross-kv)")
    return r


def capabilities(engine) -> Dict[str, Capability]:
    """Structural serving capabilities of ``engine``, with reasons.

    fully_paged     — every cache leaf of every group pages into the block
                      pool (no MLA): the tier §7 and chunked prefill need;
    prefix_cache    — radix prefix sharing would actually share (§7);
    chunked_prefill — ``prefill_chunk`` would actually chunk (the tail-
                      prefill trace exists for this architecture; §10);
    speculative     — draft/verify rounds would actually speculate (§8;
                      MLA allowed — the absorbed verify form exists);
    ep_moe          — MoE layers would route expert-parallel through the
                      shard_map all_to_all dispatch (§12): requires
                      ``moe_impl='ep'``, a pinned mesh whose ``ep_axes``
                      multiply past 1, and experts divisible by that
                      product.  Dense engines report the no-experts reason;
                      eligible engines off a mesh fall back to the pjit
                      dispatch (the serving output contract either way).

    The launcher's inert-flag warnings and the scheduler's own eligibility
    decisions both read THIS report, so they can never disagree.
    """
    strict = _tier_reasons(engine, allow_mla=False)
    with_mla = _tier_reasons(engine, allow_mla=True)
    ep = _ep_moe_reasons(engine)
    full = Capability(not strict, "; ".join(strict))
    return {
        "fully_paged": full,
        "prefix_cache": full,
        "chunked_prefill": full,
        "speculative": Capability(not with_mla, "; ".join(with_mla)),
        "ep_moe": Capability(not ep, "; ".join(ep)),
    }


def _ep_moe_reasons(engine) -> list:
    """Why ``engine`` would not decode MoE layers expert-parallel (empty
    when it would).  Mirrors ``models.blocks._ep_active`` plus the config
    preconditions, so the report and the dispatch can never disagree."""
    from repro.nn.sharding import mesh_axis_size

    cfg = engine.cfg
    r = []
    if not cfg.moe:
        r.append("no MoE layers")
        return r
    if cfg.moe_impl != "ep":
        r.append(f"moe_impl '{cfg.moe_impl}' is the pjit dispatch, not the EP shard_map")
    mesh = getattr(engine, "mesh", None)
    if mesh is None:
        r.append("no mesh pinned on the engine")
        return r
    ep = mesh_axis_size(mesh, *cfg.ep_axes)
    if ep <= 1:
        r.append(f"ep_axes {tuple(cfg.ep_axes)} multiply to 1 on this mesh")
    elif cfg.n_experts % ep:
        r.append(f"{cfg.n_experts} experts do not divide over {ep} EP shards")
    return r
