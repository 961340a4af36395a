"""Collective profile of one dry-run cell: weighted wire bytes by
(kind, dtype, shape, op_name-prefix) — the §Perf microscope.

    PYTHONPATH=src python -m benchmarks.collective_profile --arch X --shape Y
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU simulation: never take an accelerator
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import re
from collections import defaultdict


def profile(arch: str, shape: str, multi_pod: bool = False, top: int = 14, overrides=None):
    from repro.launch.dryrun import _lower_cell
    from repro.launch import hlo

    cfg, mesh, lowered, fn, fargs = _lower_cell(arch, shape, multi_pod, overrides)
    text = lowered.compile().as_text()
    comps, entry = hlo._split_computations(text)

    def cond_trip(c):
        consts = []
        for line in comps.get(c, ()):
            consts += [int(x) for x in hlo._S32_CONST_RE.findall(line)]
        return max(consts) if consts else 1

    items, edges = {}, {}
    for name, lines in comps.items():
        refs, coll = [], []
        for line in lines:
            lc = hlo._line_cost(line)
            if lc:
                shp = hlo._SHAPE_RE.findall(re.search(hlo._OP_RE, line).group(1))[-1]
                op = re.search(r'op_name="([^"]+)"', line)
                tag = ""
                if op:
                    parts = [p for p in op.group(1).split("/") if "while" not in p]
                    tag = "/".join(parts[-3:])[:60]
                coll.append((lc[0], lc[1], f"{shp[0]}[{shp[1]}]", tag))
            if "while(" in line:
                mc = re.search(r"condition=%?([\w.\-]+)", line)
                mb = re.search(r"body=%?([\w.\-]+)", line)
                trip = cond_trip(mc.group(1)) if mc else 1
                if mb:
                    refs.append((mb.group(1), trip))
            else:
                refs += [(r, 1) for r in hlo._REF_RE.findall(line)]
        items[name], edges[name] = coll, refs

    mult = {n: 0.0 for n in comps}
    mult[entry] = 1.0
    for _ in range(len(comps) + 2):
        new = {n: 0.0 for n in comps}
        new[entry] = 1.0
        for n in comps:
            for r, w in edges[n]:
                if r in new:
                    new[r] += mult[n] * w
        mult = {n: max(new[n], 1.0 if n == entry else 0.0) for n in comps}

    agg = defaultdict(float)
    for n, coll in items.items():
        for kind, b, shp, tag in coll:
            agg[(kind, shp, tag)] += b * mult[n]
    total = sum(agg.values())
    print(f"total wire bytes/device/step: {total/1e9:.2f} GB -> {total/50e9:.3f} s @50GB/s\n")
    for (kind, shp, tag), v in sorted(agg.items(), key=lambda kv: -kv[1])[:top]:
        print(f"{v/1e9:9.2f} GB  {kind:18s} {shp:28s} {tag}")
    return total


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument(
        "--set", action="append", default=[], help="config override key=value (repeatable)"
    )
    args = ap.parse_args()
    ov = dict(s.split("=", 1) for s in getattr(args, "set"))
    profile(args.arch, args.shape, args.multi_pod, overrides=ov or None)
