"""Model FLOPs of the window's decode steps (2 x active parameters per
live row plus attention at each row's real context) over the summed
``decode`` span durations times the chip's bf16 peak."""
from benchmarks.chip import counts


def read(run):
    if not run.peak:  # no chip: no share of its peak
        return None
    lo, hi = run.window
    by_index = {r.index: r for r in run.requests}
    steps = {s.number: s for s in run.steps}
    flops = secs = 0.0
    for kind, t, dur, args in run.spans:
        if kind != "decode" or not lo <= t < hi or args["step"] not in steps:
            continue
        ctx = [by_index[i].prompt_len + k for i, k in steps[args["step"]].delivered
               if k >= 1 and i in by_index]
        flops += counts.decode_model_flops(run.spec, ctx)
        secs += dur
    if not secs:
        return None
    return 100.0 * flops / (secs * run.peak["bf16_flops"])
