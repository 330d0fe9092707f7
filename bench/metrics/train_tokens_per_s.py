"""Tokens trained on all chips in the window over the window's time."""


def read(run):
    if not run.steps or run.trace:
        return None
    return sum(t for _, _, t in run.steps) / (run.window[1] - run.window[0])
