"""Mean duration of the scheduler's one-shot ``admit`` spans that start in
the window: one admission from its dispatch until its first token is on
the host, so the prefill's device time included.  Only spans that carry
a ``bucket`` are read; a program whose admit span ends at the dispatch
records none, and reads None."""


def read(run):
    lo, hi = run.window
    d = [dur for kind, t, dur, args in run.spans
         if kind == "admit" and "bucket" in args and lo <= t < hi]
    return sum(d) / len(d) * 1e3 if d else None
