"""Programs compiled or loaded from the persistent cache inside the
window, by the program's compile counter (``repro.obs.compiles``); a
program without the counter, or with its instrumentation off, reads
nothing."""


def read(run):
    try:
        from repro.obs import compiles
    except ImportError:
        return None
    from repro.obs import metrics
    if not metrics.enabled():
        return None
    if run.window is None or "train" not in run.workload:
        return None
    return float(len(compiles.programs(*run.window)))
