"""Share of the prefill work that is padding: 100 x (1 - real tail tokens
/ padded bucket tokens), summed over the ``admit`` and ``chunk`` spans
that start in the window.  An admission's real tail is its prompt less the
tokens already cached (``start``); a chunk's is its ``tail``.  None where
no span carries a ``bucket``."""


def read(run):
    lo, hi = run.window
    real = padded = 0
    for kind, t, _, args in run.spans:
        if kind not in ("admit", "chunk") or "bucket" not in args or not lo <= t < hi:
            continue
        real += args["prompt"] - args["start"] if kind == "admit" else args["tail"]
        padded += args["bucket"]
    return 100.0 * (1.0 - real / padded) if padded else None
