"""Share of the traced window in which no operation runs on a chip,
mean over the chips."""


def read(run):
    s = run.trace_summary
    if s is None or "train" not in run.workload:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
