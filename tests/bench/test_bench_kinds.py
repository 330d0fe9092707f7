"""Each kind runs a cell end to end at tiny sizes on the CPU,
through the same functions as a chip run, and comes out correct."""

import bench_helpers as bc

from bench import core


def test_train_kind_one_chip():
    run = bc.run_cell(bc.tiny_run("train1.qwen3-32b.stage"))
    assert run.correct, run.checks
    assert [c[0] for c in run.checks] == ["loss_gap", "grad_norm_gap",
                                         "grad_elem_gap", "change_gap"]
    assert run.attempted >= 1 and run.failed == 0 and run.setup_s > 0
    rate = core.load_module("metrics", "train_tokens_per_s").read(run)
    assert rate > 0


def test_serve_kind_one_chip():
    run = bc.run_cell(bc.tiny_run("serve.phi4-mini.chat", seconds=2.0))
    assert run.correct, run.checks
    assert run.attempted >= 8 and run.failed == 0
    assert all(r["stamps"] for r in run.requests)
    assert run.extra["compared_tokens"] >= 10
    for name in ("ttft_p95_ms", "itl_p95_ms",
                 "admission_wait_p95_ms.serve"):
        assert core.load_module("metrics", name).read(run) > 0, name
    # the pool compiled once: requests churning through pages never retrace
    assert run.extra["trace_counts"]["decode_slots"] == 1
