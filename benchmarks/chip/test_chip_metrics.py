"""Metric arithmetic on synthetic timestamps: TTFT censored at the
window's close, the pooled ITL tail, tokens counted inside the window
only, queue wait from admission spans, and the shares of the chip."""
import numpy as np
import pytest

from benchmarks.chip import harness, manifest
from benchmarks.chip.generator import PlannedRequest
from benchmarks.chip.xplane import DeviceOp


def req(index, due, prompt_len, times):
    r = harness.Req(PlannedRequest(index, due, np.zeros(prompt_len, np.int32), 8, -1), due)
    r.index, r.token_times = index, list(times)
    return r


def run_of(requests, **kw):
    return harness.Run(spec=kw.pop("spec", {}), traffic={}, server=kw.pop("server", {}),
                       seconds=10.0, window=(10.0, 20.0), requests=requests,
                       steps=kw.pop("steps", []), spans=kw.pop("spans", []), setup_s=42.0,
                       tail_prefill=False, peak=kw.pop("peak", {}), **kw)


def read(name, run):
    return manifest.load_reader(name)(run)


def test_tokens_per_s_counts_only_tokens_inside_the_window():
    r = run_of([req(0, 5.0, 4, [9.0, 9.9, 10.0, 15.0, 19.99, 20.0, 21.0]),
                req(1, 12.0, 4, [12.5])])
    assert read("tokens_per_s", r) == pytest.approx(4 / 10.0)


def test_ttft_censored_at_close_and_only_requests_due_inside():
    reqs = [req(0, 9.0, 4, [10.5]),  # due before the window: not counted
            req(1, 11.0, 4, [11.1]),
            req(2, 12.0, 4, [12.3]),
            req(3, 19.0, 4, []),  # never served: waits 1 s by the close
            req(4, 19.5, 4, [25.0])]  # served after the close: 0.5 s
    got = read("ttft_p50_ms", run_of(reqs))
    assert got == pytest.approx(np.percentile([0.1, 0.3, 1.0, 0.5], 50) * 1e3)
    assert got == pytest.approx(400.0)  # the median of 0.1, 0.3, 0.5 and the censored 1.0


def test_itl_pools_every_gap_that_ends_in_the_window():
    a = req(0, 10.0, 4, [10.0, 10.1, 10.2, 10.3, 12.0])  # a 1.7 s stall
    b = req(1, 10.0, 4, [9.0, 10.05, 10.10, 25.0])  # first gap ends inside, last after
    gaps = [0.1, 0.1, 0.1, 1.7, 1.05, 0.05]
    got = read("itl_p95_ms", run_of([a, b]))
    assert got == pytest.approx(np.percentile(gaps, 95) * 1e3)
    assert got > 1000.0  # the stall is in the tail


def test_setup_s_is_recorded():
    assert read("setup_s", run_of([])) == 42.0


def test_queue_wait_from_admit_and_chunk_spans():
    reqs = [req(0, 11.0, 4, []), req(1, 12.0, 4, []), req(2, 19.0, 4, [])]
    spans = [("admit", 11.2, 0.1, {"req": 0, "prompt": 4}),
             ("chunk", 12.5, 0.1, {"req": 1, "done": 0, "tail": 512}),
             ("chunk", 12.7, 0.1, {"req": 1, "done": 512, "tail": 8}),
             ("decode", 13.0, 0.01, {"step": 5})]
    got = read("sched.queue_wait_p90_ms", run_of(reqs, spans=spans))
    assert got == pytest.approx(np.percentile([0.2, 0.5, 1.0], 90) * 1e3)
    assert read("sched.queue_wait_p90_ms", run_of(reqs)) is None  # untraced


def test_decode_ms_and_step_mfu():
    spec = {"hidden_size": 2, "num_attention_heads": 1, "num_key_value_heads": 1, "head_dim": 2,
            "intermediate_size": 2, "vocab_size": 3, "num_hidden_layers": 1}
    reqs = [req(0, 10.0, 5, [10.0, 10.5]), req(1, 10.0, 9, [10.0, 10.5])]
    steps = [harness.Step(7, 10.4, 10.5, [(0, 1), (1, 1)])]
    spans = [("decode", 10.45, 0.05, {"step": 7}), ("decode", 9.0, 1.0, {"step": 1})]
    run = run_of(reqs, spec=spec, steps=steps, spans=spans, peak={"bf16_flops": 1e6})
    assert read("step.decode_ms", run) == pytest.approx(50.0)
    # active weights: 4 x (2 x 2) attention + 3 x (2 x 2) MLP + 2 x 3 head = 34
    flops = 2 * (2 * 34) + 4 * 1 * 2 * (6 + 10)
    assert read("step_mfu", run) == pytest.approx(100 * flops / (0.05 * 1e6))
    assert read("step_mfu", run_of(reqs, spec=spec, steps=steps, spans=spans)) is None


def test_idle_share_and_rooflines_need_a_trace():
    r = run_of([])
    for name in ("device.idle_frac", "fixedpoint_matmul_roofline", "paged_attention_roofline"):
        assert read(name, r) is None
    r.profile = {"ops": [DeviceOp("fusion", 0, 1, "/device:TPU:0")], "busy_s": 0.75,
                 "window_s": 1.0}
    assert read("device.idle_frac", r) == pytest.approx(25.0)
