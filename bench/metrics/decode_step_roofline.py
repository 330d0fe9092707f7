"""The decode step's share of its roofline: the least time the chip
could take for the decode calls made while the trace ran, the larger of
FLOPs over the bf16 peak and needed bytes (weights, and each active
slot's keys and values up to its position) over HBM bandwidth, against
the device time of the decode programs (profiler trace)."""

from bench.flops import decode_bytes, decode_flops


def read(run):
    s = run.trace_summary
    calls = traced_calls(run)
    if s is None or not calls:
        return None
    ns, runs = s.module("decode_slots")
    if not runs:
        return None
    p = run.peaks
    least = [max(decode_flops(run.config, ctx, n) / p["bf16_flops"],
                 decode_bytes(run.config, ctx, n) / p["hbm_bytes_per_s"])
             for _, _, n, ctx in calls]
    return 100.0 * (sum(least) / len(least)) / (ns / runs * 1e-9)


def traced_calls(run):
    lo, hi = run.extra.get("trace_host_window", (None, None))
    if lo is None:
        return []
    return [c for c in run.decode_calls if lo <= c[0] <= hi]
