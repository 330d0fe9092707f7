"""Device time per run of the pool's insert program (padded prefill and
page write), from the profiler trace."""


def read(run):
    s = run.trace_summary
    if s is None:
        return None
    ns, runs = s.module("insert")
    return ns / runs / 1e6 if runs else None
