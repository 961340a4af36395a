"""Mean duration of the scheduler's ``decode`` span in the window: one
ragged decode dispatch up to its token download, so device time included."""


def read(run):
    lo, hi = run.window
    d = [dur for kind, t, dur, _ in run.spans if kind == "decode" and lo <= t < hi]
    return sum(d) / len(d) * 1e3 if d else None
