"""The per-layer readers of what the program records about itself (its
``serve.queue`` spans and its compile counter), on hand-made records and
on each kind's run at tiny sizes on the CPU."""

import bench_helpers as bc

from bench import core
from repro.obs import metrics, timeline


def _run(window, trace_start=None, kind="serve"):
    run = core.Run(cell="c", workload={kind: {}}, config={}, traffic={},
                   seed=1, seconds=window[1] - window[0],
                   trace=trace_start is not None, chips=1)
    run.window = window
    if trace_start is not None:
        run.extra["trace_host_window"] = (trace_start, trace_start + 4.0)
    return run


def test_queue_wait_reads_spans_ending_in_the_window_before_the_profiler(
        monkeypatch):
    tl = timeline.Timeline()
    monkeypatch.setattr(timeline, "_TIMELINE", tl)
    prev = metrics.set_enabled(True)
    try:
        for i in range(20):     # inserts start at 100.5 .. 119.5 s
            tl.span("serve.queue", "serve", (100.0 + i) * 1e6,
                    0.9e6 if i in (12, 15) else 0.5e6, rid=i)
        tl.span("serve.queue", "serve", 90e6, 5e6, rid=-1)   # before
        tl.span("other", "serve", 101e6, 9e6)
    finally:
        metrics.set_enabled(prev)
    read = core.load_module("metrics", "queue_wait_p95_ms.serve").read
    assert read(_run((100.0, 130.0))) == 900.0
    # a traced run: only inserts starting before the profiler does
    assert read(_run((100.0, 130.0), trace_start=105.0)) == 500.0
    assert read(_run((200.0, 230.0))) is None


def test_compiles_count_programs_in_the_window(monkeypatch):
    tl = timeline.Timeline()
    monkeypatch.setattr(timeline, "_TIMELINE", tl)
    prev = metrics.set_enabled(True)
    try:
        for t in (5.0, 12.0, 13.0):
            tl.instant("jax.compile", "runtime", t * 1e6, duration_s=0.2)
        tl.instant("jax.cache_load", "runtime", 12.0e6, duration_s=0.1)
        serve = core.load_module("metrics", "compiles.serve").read
        train = core.load_module("metrics", "compiles.train").read
        assert serve(_run((10.0, 20.0))) == 2.0
        assert train(_run((10.0, 20.0), kind="train")) == 2.0
        assert train(_run((14.0, 20.0), kind="train")) == 0.0
        assert serve(_run((10.0, 20.0), kind="train")) is None
    finally:
        metrics.set_enabled(prev)
    with metrics.disabled():
        assert serve(_run((10.0, 20.0))) is None


def test_serve_kind_reads_queue_waits_and_no_compile_in_the_window():
    run = bc.run_cell(bc.tiny_run("serve.phi4-mini.chat", seconds=2.0))
    wait = core.load_module("metrics", "queue_wait_p95_ms.serve").read(run)
    due = core.load_module("metrics", "admission_wait_p95_ms.serve").read(run)
    # submitted when due, so a queue wait is at most the admission wait
    assert 0 < wait <= due
    assert core.load_module("metrics", "compiles.serve").read(run) == 0.0


def test_train_kind_compiles_nothing_in_the_window():
    run = bc.run_cell(bc.tiny_run("train1.qwen3-32b.stage"))
    assert run.steps
    assert core.load_module("metrics", "compiles.train").read(run) == 0.0
