"""Share of its roofline that the paged-attention kernel reached in the
traced sub-window: the least time of the window's paged-attention work
(decode rows at their real contexts, tail-prefill admissions and chunks
where the cell runs them through the kernel) over the summed device time
of the ``paged_attention*`` ops."""
from benchmarks.chip import counts, dispatches, xplane


def read(run):
    p = run.profile
    if p is None:
        return None
    spent = sum(o.dur_ns for o in xplane.kernel_ops(p["ops"], "paged_attention")) / 1e9
    if not spent:
        return None
    pool, block = run.server["kv_pool"], run.server["block_size"]
    calls = []
    for d in dispatches.in_profile(run):
        if d.kind == "decode" or d.kind == "chunk" or run.tail_prefill:
            calls += counts.paged_attention_calls(run.spec, d.rows, pool, block)
    return 100.0 * counts.least_seconds(calls, run.peak) / spent
