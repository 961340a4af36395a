"""Share of the traced sub-window in which the device runs no operation:
1 - (union of device op intervals / window), averaged over the chips."""


def read(run):
    p = run.profile
    if p is None or not p["ops"]:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
