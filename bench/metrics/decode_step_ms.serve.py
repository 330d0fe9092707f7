"""Device time per run of the pool's decode-step program
(``decode_slots``), from the profiler trace."""


def read(run):
    s = run.trace_summary
    if s is None:
        return None
    ns, runs = s.module("decode_slots")
    return ns / runs / 1e6 if runs else None
