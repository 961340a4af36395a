"""Host time of a decode step: the mean, over the ``step`` spans that start
in the window and hold a ``decode`` span, of the step's duration less the
time of its ``admit``, ``chunk`` and ``decode`` children (children carry
the step's number and start inside it).  What is left is the host's own
work in the step: table growth, token emission, finishing and eviction,
gauges, and dispatching."""

DEVICE_BOUND = ("admit", "chunk", "decode")


def read(run):
    lo, hi = run.window
    children = {}
    for kind, t, dur, args in run.spans:
        if kind in DEVICE_BOUND and "step" in args:
            children.setdefault(args["step"], []).append((kind, t, dur))
    host = []
    for kind, t, dur, args in run.spans:
        if kind != "step" or not lo <= t < hi:
            continue
        inside = [(k, d) for k, s, d in children.get(args["step"], ()) if t <= s <= t + dur]
        if any(k == "decode" for k, _ in inside):
            host.append(dur - sum(d for _, d in inside))
    return sum(host) / len(host) * 1e3 if host else None
