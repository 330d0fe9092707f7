"""95th percentile of the scheduler's own ``serve.queue`` spans (host
clock, from ``submit()`` to the start of the request's insert), over the
requests whose insert starts inside the window and before a traced run
starts its profiler: starting it stalls the host for seconds.  A program
that records no such span reads nothing."""

from bench.core import percentile


def read(run):
    from repro.obs.timeline import get_timeline
    if run.window is None:
        return None
    lo, hi = run.window[0] * 1e6, run.window[1] * 1e6
    start = run.extra.get("trace_host_window", (None,))[0]
    stop = hi if start is None else min(hi, start * 1e6)
    waits = [e.dur_us for e in get_timeline().events
             if e.name == "serve.queue" and lo <= e.ts_us + e.dur_us < stop]
    return 1e-3 * percentile(waits, 95) if waits else None
