"""The trace reduction: busy union, idle share, per-kernel sums and the
labels of idle gaps, on synthetic events and on a trace recorded on a
TPU v5 lite (fixtures/)."""
from pathlib import Path

import pytest

from benchmarks.chip import xplane
from benchmarks.chip.xplane import DeviceOp

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_kernels.xplane.pb"
DEV = "/device:TPU:0"


def ops():
    return [DeviceOp("while.6", 0, 70, "/device:TPU:2"),
            DeviceOp("fixedpoint_matmul_2bit", 0, 10, DEV),
            DeviceOp("fusion.3", 5, 10, DEV),  # overlaps the first
            DeviceOp("paged_attention_int8", 30, 20, DEV),
            DeviceOp("fixedpoint_matmul_2bit.1", 60, 5, DEV),
            DeviceOp("fixedpoint_matmul_2bit", 0, 40, "/device:TPU:1")]


def test_union_and_busy_mean_over_devices():
    assert xplane.union([(5, 15), (0, 10), (30, 50)]) == [(0, 15), (30, 50)]
    # TPU:0 busy 15 + 20 + 5 = 40 of [0, 100); TPU:1 busy 40; TPU:2 70
    assert xplane.busy_ns(ops(), 0, 100) == 50
    assert xplane.busy_ns(ops(), 0, 40) == (25 + 40 + 40) / 3  # clipped to the window


def test_gaps_and_labels():
    gaps = xplane.idle_gaps(ops(), 0, 100, DEV)
    assert gaps == [(15, 30), (50, 60), (65, 100)]
    spans = [("step", 0, 70), ("decode", 12, 35), ("wait for arrivals", 62, 100)]
    assert xplane.label_gaps(gaps, spans, n=2) == [["wait for arrivals", 35e-9],
                                                   ["decode", 15e-9]]
    assert xplane.label_gaps([(200, 210)], spans) == [["no host span", 10e-9]]


def test_kernel_sums_match_names_not_prefixes_of_other_words():
    got = xplane.kernel_ops(ops() + [DeviceOp("fixedpoint_matmulx", 0, 1, DEV)],
                            "fixedpoint_matmul")
    assert sum(o.dur_ns for o in got) == 55
    assert xplane.top_ops(ops(), 2) == [["fixedpoint_matmul_2bit", 55e-9],
                                        ["paged_attention_int8", 20e-9]]


def test_recorded_trace():
    if not FIXTURE.exists():
        pytest.fail(f"missing fixture {FIXTURE}")
    t = xplane.load(str(FIXTURE))
    assert t.devices == [DEV]
    assert {o.name for o in t.ops} >= {"fixedpoint_matmul_2bit.1", "paged_attention_int8.1"}
    mm = xplane.kernel_ops(t.ops, "fixedpoint_matmul")
    pa = xplane.kernel_ops(t.ops, "paged_attention")
    assert len(mm) == 3 and len(pa) == 3  # three traced calls of each kernel
    lo = min(o.start_ns for o in t.ops)
    hi = max(o.end_ns for o in t.ops)
    busy = xplane.busy_ns(t.ops, lo, hi)
    assert 0 < busy <= hi - lo
    assert sum(o.dur_ns for o in mm + pa) <= busy
    # the probe that recorded it opened its anchor under another name
    assert any(e.name == "bench_clock" for e in t.host)
