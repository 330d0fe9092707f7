"""The decode step's share of the bf16 peak: the algorithm's FLOPs of the
decode calls made while the trace ran, over the decode programs' device
time (profiler trace)."""

from bench.flops import decode_flops


def read(run):
    s = run.trace_summary
    lo, hi = run.extra.get("trace_host_window", (None, None))
    calls = [c for c in run.decode_calls if lo is not None and lo <= c[0] <= hi]
    if s is None or not calls:
        return None
    ns, runs = s.module("decode_slots")
    if not runs:
        return None
    flops = sum(decode_flops(run.config, ctx, n) for _, _, n, ctx in calls)
    return 100.0 * (flops / len(calls)) / (ns / runs * 1e-9) \
        / run.peaks["bf16_flops"]
