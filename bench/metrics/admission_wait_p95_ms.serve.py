"""95th percentile of the time from when a request was due to its insert
(host clock), over the requests admitted before a traced run starts its
profiler: starting it stalls the host for seconds, so a wait across it
measures the profiler and not the scheduler."""

from bench.core import percentile


def read(run):
    start = run.extra.get("trace_host_window", (None,))[0]
    waits = [r["admitted"] - r["due"] for r in run.requests
             if r["admitted"] is not None
             and (start is None or r["admitted"] < start)]
    return 1e3 * percentile(waits, 95) if waits else None
