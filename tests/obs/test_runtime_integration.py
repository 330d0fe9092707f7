"""Runtime wiring, mesh-free: the train loop and the chaos supervisor
must land their spans/counters in the default registry and timeline —
the acceptance run's "timeline contains train-step, tick and chaos-event
spans" invariant, testable without devices."""

import math
import time

import numpy as np

from repro.obs import metrics
from repro.train.runtime import TrainLoop, TrainLoopConfig


class _ToyBuilder:
    """The test_runtime quadratic toy: exercises the loop mesh-free."""

    def __call__(self, shrink):
        lr = 0.1

        def step(params, state, batch):
            x, y = batch
            w = params["w"]
            grad = 2 * (w * x - y) * x
            return ({"w": w - lr * grad.mean()},
                    {"step": state["step"] + 1},
                    {"loss": ((w * x - y) ** 2).mean()})

        def data_at(s):
            rng = np.random.RandomState(s)
            x = rng.randn(32).astype(np.float32)
            return x, 3.0 * x

        return (step, lambda key: {"w": np.float32(0.0)},
                lambda params: {"step": np.int32(0)},
                lambda b: b, data_at)


def test_train_loop_records_step_histogram_and_spans(
        tmp_path, fresh_registry, fresh_timeline):
    loop = TrainLoop(TrainLoopConfig(total_steps=2, ckpt_every=100,
                                     ckpt_dir=str(tmp_path)),
                     _ToyBuilder())
    t0 = time.perf_counter()
    loop.run(key=None)
    t1 = time.perf_counter()
    hist = fresh_registry.histograms[
        ("train_step_seconds", (("shrink", "0"),))]
    assert hist.count == 2
    spans = [e for e in fresh_timeline.events if e.name == "train_step"]
    assert len(spans) == 2
    assert all(e.lane == "train" and e.dur_us is not None for e in spans)
    assert spans[0].args["step"] == 0 and spans[1].args["step"] == 1
    # stamped on perf_counter, the clock a benchmark times its window with
    assert t0 * 1e6 <= spans[0].ts_us
    assert spans[0].ts_us + spans[0].dur_us <= spans[1].ts_us
    assert spans[1].ts_us + spans[1].dur_us <= t1 * 1e6
    for h, e in zip(loop.history, spans):
        assert math.isclose(h["dt"], e.dur_us * 1e-6, rel_tol=1e-9)


def test_train_loop_obs_disabled_records_nothing(
        tmp_path, fresh_registry, fresh_timeline):
    loop = TrainLoop(TrainLoopConfig(total_steps=2, ckpt_every=100,
                                     ckpt_dir=str(tmp_path)),
                     _ToyBuilder())
    with metrics.disabled():
        out = loop.run(key=None)
    assert out["history"][-1]["step"] == 1   # the run itself is unchanged
    assert fresh_registry.histograms == {}
    assert len(fresh_timeline) == 0
