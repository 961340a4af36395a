"""Serving telemetry end to end (DESIGN.md §13): registry-backed stats,
per-request lifecycle timelines, step-span traces, steady-state compile
flatness, and the latency_stats edge cases.

The contracts: the scheduler's legacy ``stats`` dict is a thin view over
its ``MetricsRegistry`` (same numbers in ``snapshot()`` and the
Prometheus exposition); ``Completion.timeline`` carries exactly one
``token`` event per delivered token — under preemption replays and
mid-stream cancellation included — so a timeline always reconciles with
``Completion.tokens``; TTFT is honest under chunked prefill (the first
token exists only at the FINAL chunk); a warmed fully-paged engine runs
32 mixed steps with the decode/chunk/verify compile counters flat
(admission buckets exempt — they are O(log max_len) by design); and
tracing-on exports a Chrome trace whose spans cover the serve phases.
"""
import asyncio
import dataclasses
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs, core
from repro.models import init_lm, set_packed_backend
from repro.serve import (
    AsyncServeEngine,
    Request,
    Scheduler,
    ServeConfig,
    ServeEngine,
    SpeculativeConfig,
    TelemetryConfig,
    latency_stats,
)

MAX_LEN = 24
_ENGINES = {}


@pytest.fixture
def unpack_backend():
    set_packed_backend("unpack")
    yield
    set_packed_backend("auto")


def _engine(arch="internlm2-1.8b"):
    """(qt engine, packed draft tree), cached: traces are the cost here."""
    if arch not in _ENGINES:
        cfg = configs.get_reduced(arch)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        scfg = core.SymogConfig(n_bits=2, total_steps=1)
        st = core.symog_init(params, scfg)
        qt = core.quantize_tree(params, st, scfg)
        packed = core.pack_tree(params, st, scfg)
        _ENGINES[arch] = (
            ServeEngine(cfg, qt, max_len=MAX_LEN, compute_dtype=jnp.float32),
            packed,
        )
    return _ENGINES[arch]


def _requests(cfg, key, lens=(3, 6, 4, 5), budgets=(5, 3, 6, 4), **kw):
    return [
        Request(tokens=np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                                     (L,), 0, cfg.vocab_size)),
                max_new_tokens=b, **kw)
        for i, (L, b) in enumerate(zip(lens, budgets))
    ]


def _events(comp, kind):
    return [step for ev, step in comp.timeline if ev == kind]


# ---------------------------------------------------------------------------
# registry-backed stats: one source of truth, three exports
# ---------------------------------------------------------------------------
def test_stats_dict_is_a_registry_view(rng, unpack_backend):
    eng, _ = _engine()
    comps, sched = eng.serve(_requests(eng.cfg, rng), ServeConfig(n_slots=2),
                             return_scheduler=True)
    stats, snap = sched.stats, sched.registry.snapshot()
    assert stats["tokens_emitted"] == sum(len(c.tokens) for c in comps)
    for key in ("decode_steps", "prefills", "tokens_emitted", "preemptions"):
        assert snap[f"serve_{key}"] == stats[key]
    # gauges settle to the drained state
    assert snap["serve_live_slots"] == 0 and snap["serve_pool_live_blocks"] == 0
    assert snap["serve_pool_free_blocks"] == sched.pool.n_free
    assert snap["serve_pool_bytes"] > 0
    # latency histograms: one queue/ttft sample per finished request,
    # one itl sample per decode-committed token
    assert snap["serve_queue_wait_steps"]["count"] == len(comps)
    assert snap["serve_ttft_steps"]["count"] == len(comps)
    assert snap["serve_itl_seconds"]["count"] > 0
    prom = sched.registry.to_prometheus()
    assert f"serve_tokens_emitted {stats['tokens_emitted']}" in prom
    assert 'serve_itl_seconds_bucket{le="+Inf"}' in prom
    doc = json.loads(sched.registry.to_json())
    assert doc["metrics"]["serve_decode_steps"] == stats["decode_steps"]


def test_step_time_monitor_feeds_gauges(rng, unpack_backend):
    eng, _ = _engine()
    _, sched = eng.serve(_requests(eng.cfg, rng), ServeConfig(n_slots=2),
                         return_scheduler=True)
    assert sched.monitor.count == sched.stats["decode_steps"]
    assert sched.registry.snapshot()["serve_step_time_ewma_seconds"] > 0.0
    assert 0.0 <= sched.registry.snapshot()["serve_straggler_fraction"] <= 1.0


# ---------------------------------------------------------------------------
# lifecycle timelines reconcile with Completion.tokens
# ---------------------------------------------------------------------------
def test_timeline_token_events_match_tokens(rng, unpack_backend):
    eng, _ = _engine()
    reqs = _requests(eng.cfg, rng)
    comps = eng.serve(reqs, ServeConfig(n_slots=2))
    for comp in comps:
        assert comp.timeline[0] == ("submit", 0)
        assert comp.timeline[-1][0] == "finish"
        assert len(_events(comp, "admit")) == 1
        assert len(_events(comp, "token")) == len(comp.tokens)
        steps = [s for _, s in comp.timeline]
        assert steps == sorted(steps)  # events are in step order


def test_timeline_under_preemption_replay(rng, unpack_backend):
    """The preempted request's timeline shows the preempt and the
    re-admission, and still carries exactly one token event per DELIVERED
    token (the replay re-emits nothing)."""
    eng, _ = _engine()
    reqs = _requests(eng.cfg, rng, lens=(8, 8), budgets=(16, 16))
    comps, sched = eng.serve(
        reqs, ServeConfig(n_slots=2, block_size=4, n_blocks=6), return_scheduler=True
    )
    assert sched.stats["preemptions"] >= 1
    preempted = [c for c in comps if _events(c, "preempt")]
    assert preempted
    for comp in preempted:
        admits = _events(comp, "admit")
        assert len(admits) == len(_events(comp, "preempt")) + 1
        # restart wait is visible: the completion's admitted_step is the
        # LAST admission, which is what queue_steps charges
        assert comp.admitted_step == admits[-1] > admits[0]
        assert len(_events(comp, "token")) == len(comp.tokens)
    # queue_steps charges the restart wait: the mean is over LAST admissions
    lat = latency_stats(comps)
    assert lat["queue_steps"]["mean"] == pytest.approx(
        np.mean([c.admitted_step - c.arrival for c in comps])
    )
    assert lat["queue_steps"]["p99"] > 0.0


def test_timeline_and_latency_on_cancellation(rng, unpack_backend):
    eng, _ = _engine()
    reqs = _requests(eng.cfg, rng, lens=(4, 6), budgets=(10, 10))
    sched = Scheduler(eng, ServeConfig(n_slots=2))
    ids = [sched.submit(r) for r in reqs]
    for _ in range(3):
        sched.step()
    assert sched.cancel(ids[0])
    comps = sched.run()
    by_idx = {c.index: c for c in comps}
    gone, kept = by_idx[ids[0]], by_idx[ids[1]]
    assert gone.finish_reason == "cancelled"
    assert gone.timeline[-1][0] == "cancel"
    assert len(_events(gone, "token")) == len(gone.tokens) > 0
    assert len(_events(kept, "token")) == len(kept.tokens)
    # cancelled requests never contribute a latency sample
    assert latency_stats(comps) == latency_stats([kept])
    assert latency_stats([gone]) == {}
    assert latency_stats([]) == {}
    # ... and never land in the queue/ttft histograms either
    snap = sched.registry.snapshot()
    assert snap["serve_queue_wait_steps"]["count"] == 1
    assert snap["serve_ttft_steps"]["count"] == 1


def test_ttft_honest_under_chunked_prefill(rng, unpack_backend):
    """A chunked admission spreads the prompt across steps; the first token
    exists only at the final chunk, and both the timeline and latency_stats
    must say so (no flattering queue+1 TTFT)."""
    eng, _ = _engine()
    (req,) = _requests(eng.cfg, rng, lens=(10,), budgets=(3,))
    nchunks = math.ceil(10 / 4)
    comps, sched = eng.serve([req], ServeConfig(n_slots=2, prefill_chunk=4),
                             return_scheduler=True)
    (comp,) = comps
    assert sched.stats["chunked_admissions"] == 1
    assert len(_events(comp, "chunk")) == nchunks
    assert comp.first_token_step - comp.admitted_step == nchunks - 1
    assert _events(comp, "token")[0] == comp.first_token_step
    lat = latency_stats(comps)
    assert lat["ttft_steps"]["p50"] == lat["queue_steps"]["p50"] + nchunks
    # the registry's TTFT histogram observed the same honest value
    hist = sched.registry.snapshot()["serve_ttft_steps"]
    assert hist["count"] == 1 and hist["sum"] == comp.first_token_step - comp.arrival + 1


# ---------------------------------------------------------------------------
# steady state: no recompiles after warmup (admission buckets exempt)
# ---------------------------------------------------------------------------
def test_steady_state_compile_counters_flat(rng, unpack_backend):
    """After warmup on the fully-paged tier, 32+ mixed steps (ragged
    arrivals, chunked admissions, decode) build ZERO new decode or chunk
    traces: the jit caches are steady, so serving latency can't hide a
    recompile stall."""
    eng, _ = _engine()
    lens, budgets = (3, 6, 4, 10, 5, 7), (5, 3, 6, 4, 2, 3)
    cfg = ServeConfig(n_slots=2, prefill_chunk=4)
    eng.serve(_requests(eng.cfg, rng, lens=lens, budgets=budgets), cfg)  # warmup
    sched = Scheduler(eng, cfg)
    for i, req in enumerate(_requests(eng.cfg, rng, lens=lens * 2, budgets=budgets * 2)):
        sched.submit(dataclasses.replace(req, arrival=i * 3))
    while sched._n_live or sched._queue:
        sched.step()
        assert sched.stats["decode_trace_compiles"] == 0
        assert sched.stats["chunk_trace_compiles"] == 0
    assert sched.step_count >= 32
    assert sched.stats["decode_steps"] > 0 and sched.stats["chunked_admissions"] > 0


def test_steady_state_verify_compiles_flat(rng, unpack_backend):
    """Same contract for the speculative verify trace: a second serve on
    the warmed engine commits through verify without building new traces."""
    eng, packed = _engine()
    cfg = ServeConfig(n_slots=2, speculative=SpeculativeConfig(draft=packed, k=3))
    reqs = _requests(eng.cfg, rng, lens=(3, 6, 4), budgets=(6, 4, 5))
    eng.serve(reqs, cfg)  # warmup builds the depth-k draft/verify traces
    _, sched = eng.serve(reqs, cfg, return_scheduler=True)
    assert sched.stats["spec_steps"] > 0
    assert sched.stats["verify_trace_compiles"] == 0
    assert sched.stats["decode_trace_compiles"] == 0


# ---------------------------------------------------------------------------
# tracing through a real serve
# ---------------------------------------------------------------------------
def test_trace_spans_cover_serve_phases(rng, unpack_backend, tmp_path):
    eng, _ = _engine()
    reqs = _requests(eng.cfg, rng, lens=(8, 8), budgets=(16, 16))
    tele = TelemetryConfig(trace=True, trace_capacity=512)
    comps, sched = eng.serve(
        reqs, ServeConfig(n_slots=2, block_size=4, n_blocks=6, telemetry=tele),
        return_scheduler=True,
    )
    kinds = {s[0] for s in sched.tracer.spans}
    assert {"admit", "decode"} <= kinds
    inst_kinds = {i[0] for i in sched.tracer.instants}
    assert {"evict", "preempt"} <= inst_kinds  # the pool ran hot by design
    n_decode_spans = sum(1 for s in sched.tracer.spans if s[0] == "decode")
    assert n_decode_spans == sched.stats["decode_steps"]
    path = tmp_path / "serve_trace.json"
    sched.tracer.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert {e["name"] for e in doc["traceEvents"]} >= {"admit", "decode", "evict"}
    # ring capacity bounds the event logs too, with the same drop semantics
    assert sched.events.capacity == 512
    # tracing changed no tokens
    off = eng.serve(reqs, ServeConfig(n_slots=2, block_size=4, n_blocks=6))
    assert [c.tokens for c in comps] == [c.tokens for c in off]


def test_tracing_off_by_default(rng, unpack_backend):
    eng, _ = _engine()
    _, sched = eng.serve(_requests(eng.cfg, rng), ServeConfig(n_slots=2),
                         return_scheduler=True)
    assert sched.tracer.enabled is False and len(sched.tracer) == 0
    assert sched._profile is None


# ---------------------------------------------------------------------------
# async surface
# ---------------------------------------------------------------------------
def test_async_metrics_and_timeline(rng, unpack_backend):
    eng, _ = _engine()
    reqs = _requests(eng.cfg, rng, lens=(3, 5), budgets=(4, 6))

    async def main():
        async with AsyncServeEngine(eng, ServeConfig(n_slots=2)) as srv:
            ids = [srv.submit(r) for r in reqs]
            comps = await srv.drain()
            return ids, comps, [srv.timeline(i) for i in ids], srv.metrics.snapshot()

    ids, comps, timelines, snap = asyncio.run(main())
    by_idx = {c.index: c for c in comps}
    for idx, tl in zip(ids, timelines):
        assert tl == by_idx[idx].timeline  # sealed timeline via the async surface
        assert len([1 for ev, _ in tl if ev == "token"]) == len(by_idx[idx].tokens)
    assert snap["serve_tokens_emitted"] == sum(len(c.tokens) for c in comps)
