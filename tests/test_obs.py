"""Telemetry primitives (repro.obs): metrics registry, step-span tracer,
ring logs, profile window, telemetry config (DESIGN.md §13).

Pure host-side units — no engine, no jit.  The contracts that matter:
``snapshot()`` / ``to_json()`` / ``to_prometheus()`` agree with each
other (cumulative bucket counts are cross-checkable between the dict and
the text exposition); the Chrome ``trace_event`` export round-trips
through JSON with µs timestamps and per-kind tracks; ``StatsView`` keeps
the scheduler's legacy dict shape while writing through to registry
counters; rings drop OLDEST first and count what they dropped; the null
tracer records nothing.
"""
import dataclasses
import json
import math

import pytest

from repro.obs import (
    NULL_TRACER,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    ProfileWindow,
    RingLog,
    StatsView,
    StepTracer,
    log_buckets,
    make_profile_window,
)
from repro.serve import ServeConfig, TelemetryConfig


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------
def test_log_buckets_cover_range_geometrically():
    b = log_buckets(1, 1000, factor=10.0)
    assert b == [1.0, 10.0, 100.0, 1000.0]
    assert b[-1] >= 1000
    for bad in [(0, 8), (8, 4)]:
        with pytest.raises(ValueError):
            log_buckets(*bad)
    with pytest.raises(ValueError):
        log_buckets(1, 8, factor=1.0)


def test_counter_gauge_basics():
    c, g = Counter("c"), Gauge("g")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g.set(3.5)
    g.inc(-1.0)
    assert g.value == 2.5


def test_histogram_le_semantics_and_percentiles():
    h = Histogram("h", buckets=[1, 2, 4, 8])
    for v in [0.5, 1.0, 3, 5, 100]:
        h.observe(v)
    # le semantics: 1.0 lands in the le=1 bucket, 100 in +Inf
    assert h.counts == [2, 0, 1, 1, 1]
    assert h.count == 5 and h.sum == pytest.approx(109.5)
    assert h.percentile(50) == 4  # rank 3 of 5: bucket-upper-bound estimate
    assert h.percentile(100) == math.inf  # the +Inf bucket
    assert Histogram("e", buckets=[1, 2]).percentile(50) == 0.0
    with pytest.raises(ValueError):
        Histogram("bad", buckets=[2, 1])
    with pytest.raises(ValueError):
        Histogram("dup", buckets=[1, 1, 2])


# ---------------------------------------------------------------------------
# registry exports: snapshot / json / prometheus must agree
# ---------------------------------------------------------------------------
def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("serve_tokens", "tokens emitted").inc(42)
    reg.gauge("serve_live", "live slots").set(3)
    h = reg.histogram("serve_ttft", "steps to first token", buckets=[1, 2, 4])
    for v in [1, 1, 3, 9]:
        h.observe(v)
    return reg


def test_registry_create_or_return_and_kind_conflict():
    reg = _populated_registry()
    assert reg.counter("serve_tokens") is reg.counter("serve_tokens")
    assert "serve_live" in reg and "nope" not in reg
    with pytest.raises(ValueError):
        reg.gauge("serve_tokens")  # registered as a Counter


def test_snapshot_and_json_round_trip():
    reg = _populated_registry()
    snap = reg.snapshot()
    assert snap["serve_tokens"] == 42 and snap["serve_live"] == 3
    hist = snap["serve_ttft"]
    # cumulative bucket counts, Prometheus convention
    assert hist["buckets"] == {"1.0": 2, "2.0": 2, "4.0": 3, "+Inf": 4}
    assert hist["count"] == 4 and hist["sum"] == pytest.approx(14.0)
    doc = json.loads(reg.to_json(label="unit", extra_field=7))
    assert doc["metrics"] == json.loads(json.dumps(snap))
    assert doc["label"] == "unit" and doc["extra_field"] == 7


def test_prometheus_exposition_cross_checks_snapshot():
    reg = _populated_registry()
    text = reg.to_prometheus()
    assert "# TYPE serve_tokens counter" in text
    assert "# HELP serve_tokens tokens emitted" in text
    assert "serve_tokens 42" in text
    assert "# TYPE serve_live gauge" in text
    assert "# TYPE serve_ttft histogram" in text
    # cumulative le series matches the snapshot's cumulative buckets
    assert 'serve_ttft_bucket{le="1"} 2' in text
    assert 'serve_ttft_bucket{le="4"} 3' in text
    assert 'serve_ttft_bucket{le="+Inf"} 4' in text
    assert "serve_ttft_sum 14" in text and "serve_ttft_count 4" in text
    assert text.endswith("\n")


def test_render_text_skips_zeros_and_summarizes_histograms():
    reg = _populated_registry()
    reg.counter("serve_idle")  # stays 0 -> not rendered
    lines = reg.render_text()
    joined = "\n".join(lines)
    assert "serve_tokens=42" in joined and "serve_live=3" in joined
    assert "serve_idle" not in joined
    assert any(line.startswith("serve_ttft: n=4") for line in lines)


# ---------------------------------------------------------------------------
# StatsView: the legacy dict shape over registry counters
# ---------------------------------------------------------------------------
def test_stats_view_is_a_thin_counter_view():
    reg = MetricsRegistry()
    stats = StatsView(reg, "serve_")
    stats["decode_steps"] = 0
    stats["decode_steps"] += 3
    stats["preemptions"] = 2
    assert stats["decode_steps"] == 3
    assert reg.snapshot()["serve_decode_steps"] == 3
    assert list(stats) == ["decode_steps", "preemptions"]  # first-touch order
    assert dict(stats) == {"decode_steps": 3, "preemptions": 2}
    assert stats.get("missing") is None
    with pytest.raises(KeyError):
        stats["missing"]
    # writes through the registry surface in the view too
    reg.counter("serve_decode_steps").inc()
    assert stats["decode_steps"] == 4


# ---------------------------------------------------------------------------
# rings
# ---------------------------------------------------------------------------
def test_ringlog_slices_like_a_list_and_drops_oldest():
    log = RingLog(3)
    for i in range(5):
        log.append(i)
    assert list(log) == [2, 3, 4]  # newest window
    assert log[1:] == [3, 4]  # slicing still works (list subclass)
    assert log.dropped == 2
    with pytest.raises(ValueError):
        RingLog(0)


def test_tracer_rings_bound_and_count_drops():
    tr = StepTracer(capacity=2)
    for i in range(4):
        with tr.span("decode", step=i):
            pass
        tr.instant("evict", req=i)
    assert [s[3]["step"] for s in tr.spans] == [2, 3]
    assert [i[2]["req"] for i in tr.instants] == [2, 3]
    assert tr.dropped == 4
    with pytest.raises(ValueError):
        StepTracer(capacity=0)


def test_chrome_trace_round_trip(tmp_path):
    tr = StepTracer(capacity=16)
    with tr.span("decode", step=0, n_live=2):
        pass
    tr.instant("preempt", req=1, slot=0)
    path = tmp_path / "trace.json"
    doc = tr.export_chrome(str(path))
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(doc))
    events = loaded["traceEvents"]
    assert loaded["displayTimeUnit"] == "ms"
    assert events[0]["ph"] == "M"  # process-name metadata
    span = next(e for e in events if e["ph"] == "X")
    inst = next(e for e in events if e["ph"] == "i")
    assert span["name"] == "decode" and span["args"] == {"step": 0, "n_live": 2}
    assert span["ts"] >= 0 and span["dur"] >= 0  # µs, relative to tracer t0
    assert inst["name"] == "preempt" and inst["ts"] >= span["ts"]
    assert span["tid"] != inst["tid"]  # one track per kind


def test_null_tracer_records_nothing():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("decode", step=1) as sp:
        sp.args["late"] = True  # callers may attach args mid-span
    NULL_TRACER.instant("evict", req=0)
    assert len(NULL_TRACER) == 0 and NULL_TRACER.spans == []


# ---------------------------------------------------------------------------
# profile window
# ---------------------------------------------------------------------------
def test_profile_window_arc(monkeypatch):
    calls = []
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop", None)))
    assert make_profile_window("") is None
    win = make_profile_window("/tmp/prof", n_steps=2)
    win.on_step()
    assert calls == [("start", "/tmp/prof")] and win.active
    win.on_step()  # window elapses -> stop
    assert calls[-1] == ("stop", None) and win.done and not win.active
    win.on_step()  # after done: inert
    win.stop()  # idempotent
    assert calls == [("start", "/tmp/prof"), ("stop", None)]


def test_profile_window_disarms_on_start_failure(monkeypatch):
    import jax

    def boom(d):
        raise RuntimeError("no profiler")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    win = ProfileWindow("/tmp/prof", n_steps=2)
    with pytest.raises(RuntimeError, match="could not start"):
        win.on_step()  # a requested profile never vanishes silently
    assert win.done and not win.active  # disarmed: teardown stop() is a no-op
    win.stop()
    with pytest.raises(ValueError):
        ProfileWindow("/tmp/prof", n_steps=0)


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------
def test_telemetry_config_validation():
    tele = TelemetryConfig()
    assert not tele.trace and tele.trace_capacity == 4096
    with pytest.raises(ValueError):
        TelemetryConfig(trace_capacity=0)
    with pytest.raises(ValueError):
        TelemetryConfig(profile_steps=0)
    with pytest.raises(ValueError):
        TelemetryConfig(straggler_warn=1.5)
    with pytest.raises(dataclasses.FrozenInstanceError):
        tele.trace = True
    with pytest.raises(ValueError):
        ServeConfig(telemetry={"trace": True})
