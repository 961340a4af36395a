"""One run of one benchmark cell: set-up, warm traffic, the measured
window, the traced sub-window, the comparison with the reference, and the
result line.

Set-up makes the packed weights from the seed, builds the ServeEngine and
its Scheduler, and admits one request per prefill bucket the cell's
traffic will use (which compiles or loads every program the window runs).
Then the cell's own open-loop traffic runs for the mix's ``warm_s`` and
the window opens.  Requests are submitted when their due time has passed,
between ``Scheduler.step`` calls; tokens are timestamped as the scheduler
streams them.  After the window closes, a sample of finished requests is
compared with the float32 reference, once the program's state is freed.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import generator, manifest

PROFILE_SECONDS = 3.0  # length of the traced sub-window, at the window's end
TRACE_CAPACITY = 1 << 20  # span ring large enough for a whole run


def process_start_perf() -> float:
    """perf_counter reading at which this process started (Linux /proc)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


class CompileClock:
    """Seconds and count of XLA backend compiles, from jax.monitoring."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0

        def listen(event, duration, **_):
            if "backend_compile" in event:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclasses.dataclass
class Req:
    """One request of the schedule and what happened to it."""

    plan: generator.PlannedRequest
    due: float  # perf_counter
    submitted: float = math.nan
    index: int = -1  # scheduler submission index
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: str = ""

    @property
    def prompt_len(self) -> int:
        return int(self.plan.prompt.shape[0])


@dataclasses.dataclass
class Step:
    """One Scheduler.step call: its scheduler step number, host interval,
    and the (request, token index) pairs it delivered."""

    number: int
    t0: float
    t1: float
    delivered: List[tuple]


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    spec: dict
    traffic: dict
    server: dict
    seconds: float
    window: tuple  # (open, close) perf_counter
    requests: List[Req]
    steps: List[Step]
    spans: list  # program tracer spans (kind, start, dur, args); traced run only
    setup_s: float
    tail_prefill: bool  # admissions run the paged-attention tail prefill
    peak: dict  # peaks of the device kind
    failed: int = 0  # requests the scheduler refused
    memory_peak_bytes: int = 0
    setup_parts: dict = dataclasses.field(default_factory=dict)
    profile: Optional[dict] = None  # traced sub-window reduction


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def warmup_prompts(plans, server: dict, vocab: int) -> List[np.ndarray]:
    """One prompt per prefill bucket the schedule's prompts will use, one
    token short of the bucket where that stays in it, so a prompt of a
    whole number of blocks ends one position short of a block boundary."""
    chunk, max_len = server["prefill_chunk"], server["max_len"]
    out = []
    for b in generator.prefill_buckets([p.prompt.shape[0] for p in plans], chunk, max_len):
        tail = b - 1 if b > 2 else b
        n = chunk + tail if chunk and b <= chunk else tail
        out.append(np.arange(n, dtype=np.int32) % vocab)
    return out


def _drain(sched) -> None:
    while sched.step():
        pass


class Profile:
    """The traced sub-window: jax.profiler around the window's last
    seconds, with a clock anchor for the host spans."""

    def __init__(self, start_at: float):
        self.start_at = start_at
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.t0 = self.t1 = self.anchor = math.nan
        self.active = False

    def start(self, sync) -> None:
        import jax

        sync()
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation("chipbench_clock_anchor"):
            self.anchor = time.perf_counter()
        self.t0 = time.perf_counter()
        self.active = True

    def stop(self, sync) -> None:
        import jax

        sync()
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self, run: Run, extra_spans) -> dict:
        from benchmarks.chip import xplane

        t = xplane.load(xplane.find_xplane(self.dir))
        off = xplane.clock_offset_ns(t, self.anchor)
        if off is None:
            raise RuntimeError("the clock anchor annotation is missing from the trace")
        lo, hi = self.t0 * 1e9 + off, self.t1 * 1e9 + off
        ops = [o for o in t.ops if o.end_ns > lo and o.start_ns < hi]
        spans = [(k, s * 1e9 + off, (s + d) * 1e9 + off) for k, s, d, _ in run.spans]
        spans += [(k, s * 1e9 + off, e * 1e9 + off) for k, s, e in extra_spans]
        gaps = xplane.idle_gaps(ops, lo, hi, t.devices[0]) if t.devices else []
        return {
            "ops": ops,
            "devices": t.devices,
            "busy_s": xplane.busy_ns(ops, lo, hi) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "t0": self.t0,
            "t1": self.t1,
            "device_ops": xplane.top_ops(ops),
            "idle_gaps": xplane.label_gaps(gaps, spans),
        }

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


class Session:
    """The process's hold on the chip: devices, the peak table entry, the
    compile cache and the compile clock, shared by every serve pass."""

    def __init__(self, cell: manifest.Cell, require_tpu: bool = True,
                 reuse_engine: bool = False):
        import jax

        t = time.perf_counter()
        self.cell = cell
        self.devices = jax.devices()
        self.error = ""
        if require_tpu and self.devices[0].platform != "tpu":
            self.error = (f"no TPU (JAX sees {self.devices[0].platform}); the benchmark "
                          "never runs on the CPU")
        elif len(self.devices) < cell.chips:
            self.error = f"{cell.name} needs {cell.chips} chips, JAX sees {len(self.devices)}"
        self.require_tpu = require_tpu
        self.reuse_engine = reuse_engine
        self._engine = None
        if self.error:
            return
        from benchmarks.chip import peaks
        from repro.launch.compile_cache import enable_compile_cache

        self.peak, self.cache = {}, ""
        if require_tpu:  # CPU passes (the tests) keep no persistent cache
            self.peak = peaks.peak_for(self.devices[0].device_kind)
            self.cache = enable_compile_cache()
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.clock = CompileClock()
        self.init_s = time.perf_counter() - t

    def engine(self, cfg, params, max_len: int):
        """A ServeEngine over ``params``.  With ``reuse_engine`` (the tools
        that serve many seeds in one process) an engine of the same
        configuration is kept and only its params are replaced: its
        compiled programs take the params as an argument."""
        from repro.serve import ServeEngine

        eng = self._engine
        if self.reuse_engine and eng is not None and (eng.cfg, eng.max_len) == (cfg, max_len):
            eng.params = params
            return eng
        eng = ServeEngine(cfg, params, max_len=max_len)
        self._engine = eng if self.reuse_engine else None
        return eng


def serve(session: Session, seed: int, seconds: float, traced: bool, *,
          traffic: Optional[dict] = None, t_start: Optional[float] = None) -> Run:
    """Set up the cell from ``seed``, run its warm traffic and measured
    window (and with ``traced`` the profiled sub-window), free the
    program's state and return what was recorded.  ``traffic`` replaces
    the cell's mix (the knee sweep offers other rates)."""
    import jax

    from benchmarks.chip import artifact
    from repro.serve import Request, Scheduler, ServeConfig
    from repro.serve.config import TelemetryConfig

    cell, clock = session.cell, session.clock
    spec = cell.spec
    traffic = traffic or cell.traffic
    server = traffic["server"]
    t_start = time.perf_counter() if t_start is None else t_start
    parts: Dict[str, float] = {"jax_init_s": session.init_s}
    compiles0, compile_s0 = clock.count, clock.seconds

    cfg = artifact.model_config(spec, server)
    t = time.perf_counter()
    params = artifact.build_params(cfg, spec, seed)
    parts["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    eng = session.engine(cfg, params, server["max_len"])
    if session.require_tpu and (eng.backend, eng.attn_backend) != ("pallas", "fused"):
        raise RuntimeError(f"engine backends {eng.backend}/{eng.attn_backend}, "
                           "want pallas/fused")
    reqs = [Req(p, math.nan) for p in generator.plan(traffic, seconds, seed, spec["vocab_size"])]
    by_index: Dict[int, Req] = {}
    delivered: List[tuple] = []

    def on_token(idx: int, tok: int) -> None:
        r = by_index.get(idx)
        if r is not None:
            r.token_times.append(time.perf_counter())
            r.tokens.append(int(tok))
            delivered.append((idx, len(r.tokens) - 1))

    def on_finish(comp) -> None:
        r = by_index.get(comp.index)
        if r is not None:
            r.finish = comp.finish_reason

    sched = Scheduler(eng, ServeConfig(
        n_slots=server["n_slots"], temperature=0.0, block_size=server["block_size"],
        n_blocks=server["n_blocks"], prefill_chunk=server["prefill_chunk"],
        prefix_cache=server["prefix_cache"], on_token=on_token,
        telemetry=TelemetryConfig(trace=traced, trace_capacity=TRACE_CAPACITY,
                                  straggler_warn=0.0)))
    caps = eng.capabilities()
    tail_prefill = bool(caps["fully_paged"]) and (eng.kv_quant_bits > 0 or sched.chunk > 0)
    parts["engine_s"] = time.perf_counter() - t

    t = time.perf_counter()
    # two decode steps each: a prompt ending one short of a block boundary
    # then grows its block table, so that path is compiled too
    for prompt in warmup_prompts([r.plan for r in reqs], server, spec["vocab_size"]):
        sched.submit(Request(tokens=prompt, max_new_tokens=3))
    _drain(sched)
    jax.block_until_ready(sched.caches)
    parts["shape_warmup_s"] = time.perf_counter() - t
    parts["compile_s"] = clock.seconds - compile_s0
    parts["compiles"] = clock.count - compiles0
    parts["warm_traffic_s"] = traffic["warm_s"]
    compiles1 = clock.count

    def sync():
        jax.block_until_ready(sched.caches)

    # ---- warm traffic, then the window (open loop on the wall clock) ----
    t0 = time.perf_counter()
    for r in reqs:
        r.due = t0 + r.plan.due_s
    w_open = t0 + traffic["warm_s"]
    w_close = w_open + seconds
    profile = Profile(max(w_open, w_close - PROFILE_SECONDS)) if traced else None
    steps: List[Step] = []
    host_spans: List[tuple] = []
    failed, i, n = 0, 0, len(reqs)
    while True:
        now = time.perf_counter()
        if i < n and reqs[i].due <= now:
            ts = now
            while i < n and reqs[i].due <= now:
                r = reqs[i]
                r.submitted = time.perf_counter()
                try:
                    r.index = sched.submit(Request(tokens=r.plan.prompt,
                                                   max_new_tokens=r.plan.max_new_tokens),
                                           on_finish=on_finish)
                    by_index[r.index] = r
                except ValueError as e:
                    failed += 1
                    say(f"chipbench: request {r.plan.index} refused: {e}")
                i += 1
            host_spans.append(("submit", ts, time.perf_counter()))
        if now >= w_close:
            break
        if profile is not None and not profile.active and now >= profile.start_at:
            profile.start(sync)
        delivered.clear()
        number, ts = sched.step_count, time.perf_counter()
        busy = sched.step()
        te = time.perf_counter()
        if delivered:
            steps.append(Step(number, ts, te, list(delivered)))
        host_spans.append(("step", ts, te))
        if not busy:
            nxt = reqs[i].due if i < n else w_close
            ts = time.perf_counter()
            time.sleep(max(0.0, min(nxt, w_close) - ts))
            host_spans.append(("wait for arrivals", ts, time.perf_counter()))
    if profile is not None and profile.active:
        profile.stop(sync)
    sync()
    parts["window_compiles"] = clock.count - compiles1
    mem = [d.memory_stats() or {} for d in session.devices[: cell.chips]]
    run = Run(spec=spec, traffic=traffic, server=server, seconds=seconds,
              window=(w_open, w_close), requests=reqs, steps=steps,
              spans=list(sched.tracer.spans) if traced else [], setup_s=w_open - t_start,
              tail_prefill=tail_prefill, peak=session.peak, failed=failed,
              memory_peak_bytes=max(int(m.get("peak_bytes_in_use", 0)) for m in mem),
              setup_parts=parts)
    # free the program's state before anything else runs on the chip
    eng.params = None
    del sched, eng, params
    gc.collect()
    if profile is not None:
        try:
            if profile.t1 == profile.t1:  # the sub-window was traced
                run.profile = profile.reduce(run, host_spans)
        finally:
            profile.close()
    return run


def report(session: Session, run: Run, seed: int, traced: bool, out=None) -> None:
    """Compare with the reference, read the cell's metrics, print the
    set-up parts, the generator's lateness, the checks (last on standard
    error) and the result line (last on standard output)."""
    from benchmarks.chip import compare

    out = out or sys.stdout
    cell = session.cell
    lo, hi = run.window
    say("chipbench setup: " + json.dumps({"setup_s": run.setup_s, **run.setup_parts,
                                          "compile_cache": session.cache}))
    late = np.asarray([r.submitted - r.due for r in run.requests
                       if lo <= r.due < hi and not math.isnan(r.submitted)])
    if late.size:
        say(f"chipbench generator lateness: p50 {np.median(late) * 1e3:.3f} ms, "
            f"p99 {np.percentile(late, 99) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms "
            f"over {late.size} submits")
    checks = compare.check(run, seed)
    metrics = {}
    for m in cell.metrics(traced):
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    d = session.devices[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": cell.chips,
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": sum(1 for r in run.requests if lo <= r.due < hi),
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.profile is not None:
        device["busy_s"] = run.profile["busy_s"]
        device["window_s"] = run.profile["window_s"]
        result["breakdown"] = {"device_ops": run.profile["device_ops"],
                               "idle_gaps": run.profile["idle_gaps"]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    for k, c in checks.items():
        say(f"check {k}: {c['value']!r} {c['op']} limit {c['limit']!r} -> "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(result), file=out, flush=True)


def run_cell(cell: manifest.Cell, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True, t_start: Optional[float] = None, out=None) -> int:
    """Run ``cell`` once and print its result line; returns the exit code."""
    t_start = process_start_perf() if t_start is None else t_start
    session = Session(cell, require_tpu)
    if session.error:
        say(f"chipbench: {session.error}")
        return 2
    run = serve(session, seed, seconds, traced, t_start=t_start)
    report(session, run, seed, traced, out)
    return 0
