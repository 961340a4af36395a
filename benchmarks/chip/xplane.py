"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-kernel sums and idle gaps.

Device operations are the events of the "XLA Ops" line of each
``/device:TPU:<n>`` plane.  Their timestamps are on the profiler's clock;
``clock_offset`` maps the host's ``time.perf_counter`` onto it through an
annotation the harness opens at a known perf_counter reading, so the
program's host spans can label the device's idle gaps.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
ANCHOR = "chipbench_clock_anchor"
# an op event is named by its HLO text, "%<name> = <shape> <opcode>(...)"
HLO_NAME = re.compile(r"^%?([^\s=]+)")
# control-flow ops span the ops of their bodies on the same line
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


@dataclasses.dataclass
class DeviceOp:
    name: str  # the HLO instruction name, e.g. ``fixedpoint_matmul_2bit.1``
    start_ns: float
    dur_ns: float
    device: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class HostEvent:
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Trace:
    ops: List[DeviceOp]
    host: List[HostEvent]
    devices: List[str]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    """Device operations and host events of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, host, devices = [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(DeviceOp(HLO_NAME.match(e.name).group(1), e.start_ns,
                                        e.duration_ns, plane.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(HostEvent(e.name, e.start_ns, e.duration_ns) for e in line.events)
    return Trace(ops=ops, host=host, devices=devices)


def clock_offset_ns(trace: Trace, anchor_perf_s: float) -> Optional[float]:
    """Profiler-clock ns minus perf_counter ns, from the anchor annotation."""
    for e in trace.host:
        if e.name == ANCHOR:
            return e.start_ns - anchor_perf_s * 1e9
    return None


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(ops: Sequence[DeviceOp], lo: float, hi: float) -> float:
    """Mean over devices of the union of op intervals inside [lo, hi)."""
    by_dev: Dict[str, list] = {}
    for o in ops:
        by_dev.setdefault(o.device, []).append((o.start_ns, o.end_ns))
    if not by_dev:
        return 0.0
    tot = sum(sum(e - s for s, e in clip(union(iv), lo, hi)) for iv in by_dev.values())
    return tot / len(by_dev)


def idle_gaps(ops: Sequence[DeviceOp], lo: float, hi: float, device: str):
    """(start, end) of the gaps in [lo, hi) where ``device`` runs no op."""
    busy = clip(union((o.start_ns, o.end_ns) for o in ops if o.device == device), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def kernel_ops(ops: Sequence[DeviceOp], kernel: str) -> List[DeviceOp]:
    """Ops of one Pallas kernel: the kernel's ``name`` opens the op name."""
    pat = re.compile(rf"^{re.escape(kernel)}(\b|_|\.|$)")
    return [o for o in ops if pat.match(o.name)]


def top_ops(ops: Sequence[DeviceOp], n: int = 10) -> List[Tuple[str, float]]:
    """[name, seconds] of the ops that took most device time, op names
    grouped without their numeric suffix (``fusion.12`` -> ``fusion``);
    control-flow ops, whose time their bodies' ops already hold, are left
    out."""
    tot: Dict[str, float] = {}
    for o in ops:
        if CONTAINERS.match(o.name):
            continue
        key = re.sub(r"\.\d+$", "", o.name)
        tot[key] = tot.get(key, 0.0) + o.dur_ns / 1e9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def label_gaps(gaps, spans: Sequence[Tuple[str, float, float]], n: int = 10):
    """[label, seconds] of the ``n`` longest gaps; the label is the
    innermost host span (name, start_ns, end_ns) open at the gap's middle,
    or 'no host span'."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        label = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else "no host span"
        out.append([label, (e - s) / 1e9])
    return out
