"""Readers and compared numbers on hand-made run records."""

import math

import numpy as np

from bench import core
from bench.kinds import train as KT


def _run(requests, trace_start=None):
    run = core.Run(cell="c", workload={}, config={}, traffic={}, seed=1,
                   seconds=10.0, trace=trace_start is not None, chips=1)
    run.requests = requests
    if trace_start is not None:
        run.extra["trace_host_window"] = (trace_start, trace_start + 4.0)
    return run


def test_admission_wait_leaves_out_waits_across_the_profiler_start():
    """A request due before the profiler starts but admitted after it has
    waited through the stall that starting it causes: it is left out."""
    read = core.load_module("metrics", "admission_wait_p95_ms.serve").read
    reqs = [{"due": float(i), "admitted": i + 0.01 * (i + 1)}
            for i in range(20)]
    reqs.append({"due": 19.5, "admitted": 27.0})      # across the start
    reqs.append({"due": 21.0, "admitted": 26.0})      # after the start
    reqs.append({"due": 5.5, "admitted": None})       # never admitted
    assert math.isclose(read(_run(reqs, trace_start=20.5)), 190.0)
    untraced = read(_run(reqs))
    assert untraced >= 4999.0


def test_element_gap_sees_rounding_that_norms_average_out():
    """An error of random sign on every element leaves the norm all but
    unchanged, and the element gap reads its size; a leaf sampled in part
    is scaled to its whole size."""
    rng = np.random.default_rng(0)
    size = 1 << 16
    ref_g = [rng.standard_normal(size) for _ in range(3)]
    noisy = [g * (1 + 0.05 * rng.choice([-1.0, 1.0], g.size))
             for g in ref_g]
    ref = {"grad_norms": [float(np.linalg.norm(g)) for g in ref_g],
           "grad_sample": ref_g, "sizes": [size] * 3}
    prog = {"grad_norms": [float(np.linalg.norm(g)) for g in noisy],
            "grad_sample": noisy, "sizes": [size] * 3}
    assert KT.gap(prog["grad_norms"], ref["grad_norms"]) < 0.005
    assert 0.045 < KT.elem_gap(prog, ref) < 0.055
    half = dict(ref, grad_sample=[v[:size // 2] for v in ref_g])
    part = dict(prog, grad_sample=[v[:size // 2] for v in noisy])
    assert 0.045 < KT.elem_gap(part, half) < 0.055


def test_sample_index_is_drawn_from_the_seed_within_each_leaf():
    c = core.load_cell("train1.qwen3-32b.stage")["config"]
    c = dict(c, hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=5000)
    a, b = KT.sample_index(c, 3), KT.sample_index(c, 3)
    other = KT.sample_index(c, 4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    big = [i for i, x in enumerate(a) if len(x) == KT.SAMPLE]
    assert big and any(not np.array_equal(a[i], other[i]) for i in big)
    small = [x for x in a if len(x) < KT.SAMPLE]
    assert all(np.array_equal(x, np.arange(len(x))) for x in small)
