"""Largest share of the KV block pool held by live requests: the most
``blocks_live`` of any ``step`` span that starts in the window (read once
the step's growth and admissions are in), over the pool's blocks
(``n_blocks``, or the dense-equivalent ``n_slots x ceil(max_len /
block_size)`` when that is 0)."""


def read(run):
    lo, hi = run.window
    live = [args["blocks_live"] for kind, t, _, args in run.spans
            if kind == "step" and "blocks_live" in args and lo <= t < hi]
    if not live:
        return None
    s = run.server
    blocks = s["n_blocks"] or s["n_slots"] * -(-s["max_len"] // s["block_size"])
    return 100.0 * max(live) / blocks
