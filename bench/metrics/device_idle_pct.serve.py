"""Share of the traced window in which no operation runs on the chip."""


def read(run):
    s = run.trace_summary
    if s is None or "serve" not in run.workload:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
