"""95th percentile over every gap between consecutive tokens of a
request, both served in the window."""

from bench.core import percentile


def read(run):
    if not run.requests or run.trace:
        return None
    hi = run.window[1]
    gaps = [b - a for r in run.requests
            for a, b in zip(r["stamps"], r["stamps"][1:]) if b <= hi]
    return 1e3 * percentile(gaps, 95) if gaps else None
