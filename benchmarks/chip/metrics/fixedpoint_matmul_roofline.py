"""Share of its roofline that the packed-matmul kernel reached in the
traced sub-window: the least time the window's packed-matmul work could
take (per call, the larger of ops at the bf16 peak and bytes at the HBM
peak, counted from real rows) over the summed device time of the
``fixedpoint_matmul_*`` ops."""
from benchmarks.chip import counts, dispatches, xplane


def read(run):
    p = run.profile
    if p is None:
        return None
    spent = sum(o.dur_ns for o in xplane.kernel_ops(p["ops"], "fixedpoint_matmul")) / 1e9
    if not spent:
        return None
    calls = []
    for d in dispatches.in_profile(run):
        calls += counts.fixedpoint_matmul_calls(run.spec, d.tokens, d.head_rows)
    return 100.0 * counts.least_seconds(calls, run.peak) / spent
