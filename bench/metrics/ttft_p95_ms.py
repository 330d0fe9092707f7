"""95th percentile, over every request due in the window, of the time
from when it was due to its first token (the generator's lateness
included)."""

from bench.core import percentile


def read(run):
    if not run.requests or run.trace:
        return None
    return 1e3 * percentile(
        [r["stamps"][0] - r["due"] if r["stamps"] else float("inf")
         for r in run.requests], 95)
