"""int8-on-the-wire bucketed train step.

The quantized-wire PR's acceptance bar, on the real step:

* **fused vs shmap bit parity** — one optimizer step with
  ``wire_dtype="int8"`` produces byte-identical params, optimizer state
  AND error-feedback residuals under backend ``bine`` vs ``pallas_fused``
  (shared chunk rule + pow2 scales make the two codec paths decode the
  same bits).
* **EF plumbing** — ``state["ef"]`` exists exactly for int8-wire buckets,
  is float32, survives the step, and is non-zero after a real gradient
  (quantization actually left a residual behind).
* **loss tracking** — 200 steps on the toy model: the int8-wire run's
  final loss stays within 2% of the float32 run (error feedback keeps
  the quantization noise unbiased instead of accumulating).
* **config validation** — the silent fall-through is gone: unsupported
  wire dtypes and unsupported (backend, wire) combinations raise at
  ``TrainConfig`` construction, and int8 on a non-pow2 data axis raises
  at ``make_train_step``.
"""

import pytest

from repro.train.step import WIRE_DTYPES, TrainConfig


def test_trainconfig_rejects_bad_wire():
    with pytest.raises(ValueError, match="wire_dtype"):
        TrainConfig(wire_dtype="int4")
    with pytest.raises(ValueError, match="int8"):
        TrainConfig(backend="xla", wire_dtype="int8")
    with pytest.raises(ValueError, match="bucket"):
        TrainConfig(backend="bine", wire_dtype="int8", bucket_bytes=0)
    for w in WIRE_DTYPES:
        TrainConfig(backend="bine", wire_dtype=w)   # all valid spellings


_PARITY = r"""
import jax, numpy as np
from jax.sharding import Mesh
from repro.configs import base
from repro.models import transformer as T
from repro.train.step import (TrainConfig, bucket_report, make_train_step,
                              make_init_fns)
from jax import set_mesh
from repro.train.data import DataConfig, make_batch
from repro.optim.adamw import AdamWConfig

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
cfg = base.reduced(base.get_config("phi4-mini-3.8b")).replace(dtype="float32")
acfg = AdamWConfig(lr=3e-3, warmup_steps=1, total_steps=100)
key = jax.random.key(0)
params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
dcfg = DataConfig(global_batch=8, seq_len=32, vocab_size=cfg.vocab_size)

def one_step(backend, wire, bb=-1):
    tcfg = TrainConfig(backend=backend, dp_axes=("pod", "data"), adamw=acfg,
                       bucket_bytes=bb, wire_dtype=wire)
    step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, params_shapes)
    init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)
    with set_mesh(mesh):
        params = init_p(key)
        state = init_s(params)
        b = make_batch(dcfg, 0)
        batch = {k: jax.device_put(v, shardings["batch"][k])
                 for k, v in b.items()}
        params, state, metrics = step_fn(params, state, batch)
        return (jax.tree.map(np.asarray, params),
                jax.tree.map(np.asarray, state["opt"]),
                {k: np.asarray(v) for k, v in state.get("ef", {}).items()},
                float(metrics["loss"]), shardings["bucket_plan"], tcfg)

ref_p, ref_o, ref_ef, ref_loss, plan, tcfg = one_step("bine", "int8")
assert plan is not None and len(plan.buckets) >= 1

# EF rows exist for every int8-wire bucket, float32, and quantization
# actually left a residual behind after one real gradient
assert set(ref_ef) == {str(b.bid) for b in plan.buckets}, ref_ef.keys()
for v in ref_ef.values():
    assert v.dtype == np.float32
assert sum(float(np.abs(v).sum()) for v in ref_ef.values()) > 0.0

# bucket_report carries the wire columns
rep = bucket_report(tcfg, plan)
assert all(r["rs_wire"] == "int8" and r["ag_wire"] == "int8" for r in rep)
assert all(r["rs_wire_provenance"] == "fixed" for r in rep)

# fused vs shmap codec paths: byte-identical params, opt state, EF (the
# fused bucket path runs the bine schedule, so the shmap twin is "bine";
# recdoub is a different schedule -> different quantize points, checked
# below to tolerance only)
p2, o2, ef2, loss2, _, _ = one_step("pallas_fused", "int8")
for x, y in zip(jax.tree.leaves(ref_p) + jax.tree.leaves(ref_o),
                jax.tree.leaves(p2) + jax.tree.leaves(o2)):
    assert x.dtype == y.dtype
    assert np.array_equal(x, y), ("pallas_fused", x.shape)
assert set(ef2) == set(ref_ef)
for k in ref_ef:
    assert np.array_equal(ref_ef[k], ef2[k]), ("pallas_fused", k)
assert loss2 == ref_loss

# wire="auto" resolves per bucket and runs (decision may be any wire)
pa, oa, efa, loss_a, plan_a, tcfg_a = one_step("auto", "auto")
rep = bucket_report(tcfg_a, plan_a)
assert all(r["rs_wire"] in ("float32", "bfloat16", "int8") for r in rep)
assert all(r["rs_wire_provenance"] in ("analytic", "measured") for r in rep)
assert np.isfinite(loss_a)

# f32 reference for sanity: one int8 step must not wreck the loss, on
# either codec schedule family
_, _, _, f32_loss, _, _ = one_step("bine", "float32")
assert abs(ref_loss - f32_loss) / abs(f32_loss) < 0.01, (ref_loss, f32_loss)
_, _, ef_rd, rd_loss, _, _ = one_step("recdoub", "int8")
assert set(ef_rd) == set(ref_ef)
assert abs(rd_loss - f32_loss) / abs(f32_loss) < 0.01, (rd_loss, f32_loss)

# int8 + non-pow2 data axis: loud, at trace time
mesh6 = Mesh(np.asarray(jax.devices()[:6]).reshape(1, 6, 1),
             ("pod", "data", "model"))
try:
    make_train_step(cfg, TrainConfig(backend="bine", dp_axes=("pod", "data"),
                                     wire_dtype="int8", bucket_bytes=-1),
                    mesh6, params_shapes)
except ValueError as e:
    assert "pow" in str(e) or "power" in str(e), e
else:
    raise AssertionError("int8 wire on n_dp=6 did not raise")
print("PARITY_OK")
"""


def test_int8_step_fused_vs_shmap_bitwise(subproc):
    out = subproc(_PARITY, devices=8, timeout=2400)
    assert "PARITY_OK" in out


_EF_200 = r"""
import jax, numpy as np
from repro.configs import base
from repro.models import transformer as T
from repro.train.step import TrainConfig, make_train_step, make_init_fns
from jax import set_mesh
from repro.train.data import DataConfig, make_batch
from repro.optim.adamw import AdamWConfig

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
cfg = base.reduced(base.get_config("phi4-mini-3.8b")).replace(
    dtype="float32", n_layers=2)
acfg = AdamWConfig(lr=1e-2, warmup_steps=10, total_steps=250)
key = jax.random.key(0)
params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
dcfg = DataConfig(global_batch=8, seq_len=32, vocab_size=cfg.vocab_size)
STEPS = 200

def run(wire):
    tcfg = TrainConfig(backend="bine", dp_axes=("pod", "data"), adamw=acfg,
                       bucket_bytes=-1, wire_dtype=wire)
    step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, params_shapes)
    init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)
    with set_mesh(mesh):
        params = init_p(key)
        state = init_s(params)
        losses = []
        for i in range(STEPS):
            b = make_batch(dcfg, i)
            batch = {k: jax.device_put(v, shardings["batch"][k])
                     for k, v in b.items()}
            params, state, metrics = step_fn(params, state, batch)
            losses.append(float(metrics["loss"]))
    return losses

f32 = run("float32")
i8 = run("int8")
assert f32[-1] < f32[0], "f32 run did not learn; test is vacuous"
rel = abs(i8[-1] - f32[-1]) / abs(f32[-1])
print(f"final f32={f32[-1]:.5f} int8={i8[-1]:.5f} rel={rel:.4f}")
assert rel < 0.02, (f32[-1], i8[-1], rel)
print("EF200_OK")
"""


def test_int8_ef_200_steps_tracks_f32_loss(subproc):
    """200 toy-model steps: error feedback keeps the int8-wire loss curve
    within 2% of the float32 run (the acceptance bound)."""
    out = subproc(_EF_200, devices=8, timeout=3600)
    assert "EF200_OK" in out
