"""Integration: 2x2x2 mesh training — loss decreases, backends agree."""

import pytest

CODE = r"""
import jax, numpy as np
from repro.configs import base
from repro.models import transformer as T
from repro.train.step import TrainConfig, make_train_step, make_init_fns
from jax import set_mesh
from repro.train.data import DataConfig, make_batch
from repro.optim.adamw import AdamWConfig

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = base.reduced(base.get_config("phi4-mini-3.8b"))
acfg = AdamWConfig(lr=3e-3, warmup_steps=1, total_steps=100)
key = jax.random.key(0)
params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
dcfg = DataConfig(global_batch=8, seq_len=64, vocab_size=cfg.vocab_size)

results = {}
for backend in ("bine", "xla", "bine_hier", "auto"):
    tcfg = TrainConfig(backend=backend, dp_axes=("pod", "data"), adamw=acfg)
    step_fn, shardings, layout = make_train_step(cfg, tcfg, mesh, params_shapes)
    init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)
    with set_mesh(mesh):
        params = init_p(key)
        state = init_s(params)
        losses = []
        for s in range(12):
            b = make_batch(dcfg, s)
            batch = {k: jax.device_put(v, shardings["batch"][k])
                     for k, v in b.items()}
            params, state, metrics = step_fn(params, state, batch)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.05, (backend, losses)
    assert all(np.isfinite(losses)), (backend, losses)
    results[backend] = losses
for b in ("xla", "bine_hier", "auto"):
    diff = max(abs(a - c) for a, c in zip(results["bine"], results[b]))
    assert diff < 0.05, (b, diff)

# gradient accumulation path
tcfg = TrainConfig(backend="bine", dp_axes=("pod", "data"), adamw=acfg,
                   accum_steps=2)
step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, params_shapes)
init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)
with set_mesh(mesh):
    params = init_p(key); state = init_s(params)
    b = make_batch(dcfg, 0)
    batch = {k: jax.device_put(v, shardings["batch"][k]) for k, v in b.items()}
    params, state, metrics = step_fn(params, state, batch)
    assert np.isfinite(float(metrics["loss"]))

# bf16 wire compression path
tcfg = TrainConfig(backend="bine", dp_axes=("pod", "data"), adamw=acfg,
                   wire_dtype="bfloat16")
step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, params_shapes)
init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)
with set_mesh(mesh):
    params = init_p(key); state = init_s(params)
    losses = []
    for s in range(6):
        b = make_batch(dcfg, s)
        batch = {k: jax.device_put(v, shardings["batch"][k]) for k, v in b.items()}
        params, state, metrics = step_fn(params, state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
print("ALL_OK")
"""


def test_train_backends(subproc):
    out = subproc(CODE, devices=8, timeout=1500)
    assert "ALL_OK" in out


BF16_OVERFLOW = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.train.step import (TrainConfig, _post_reduce_div, _rs_leaf,
                              _wire_cast)

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
p = 4
tcfg = TrainConfig(backend="bine", dp_axes=("data",), wire_dtype="bfloat16")

def reduced_mean(zd):
    def f(g):
        out = _rs_leaf(tcfg, g.reshape(g.shape[-1]), zd, p)
        return out.astype(jnp.float32) / _post_reduce_div(tcfg, p)
    # zd >= 0: ranks hold disjoint blocks -> global (64,). zd < 0: the
    # allreduced leaf is replicated; P("data") just stacks the copies.
    return jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data")))

# large-magnitude grads: the SUM of 4 bf16 grads overflows bf16's range
# (max ~3.39e38) but the mean does not — the pre-scale keeps it finite
big = np.full((4, 64), 2.5e38, np.float32)
for zd in (-1, 0):
    fn = reduced_mean(zd)
    out = np.asarray(fn(big)).reshape(-1)
    assert np.all(np.isfinite(out)), (zd, out[:4])
    np.testing.assert_allclose(out, 2.5e38, rtol=0.02)
    # post-hoc division (the old behavior) cannot recover: the reduce
    # itself saturates
    naive = (jnp.asarray(big, jnp.bfloat16).astype(jnp.float32).sum(0)
             .astype(jnp.bfloat16))
    assert np.all(np.isinf(np.asarray(naive, np.float32)))

# small-magnitude sanity: bf16 wire mean matches the fp32 mean within
# bf16 resolution
rng = np.random.RandomState(0)
g = rng.randn(4, 64).astype(np.float32)
out = np.asarray(reduced_mean(0)(g)).reshape(-1)
np.testing.assert_allclose(out, g.mean(0), rtol=0.05, atol=0.02)
print("BF16_OK")
"""


def test_bf16_wire_prescale_no_overflow(subproc):
    out = subproc(BF16_OVERFLOW, devices=4, timeout=600)
    assert "BF16_OK" in out


TP_AUTO = r"""
import jax, numpy as np
from repro.configs import base
from repro.models import transformer as T
from repro.train.step import (TrainConfig, bucket_decisions, for_mesh,
                              make_init_fns, make_train_step)
from jax import set_mesh
from repro.train.data import DataConfig, make_batch
from repro.launch.mesh import make_mesh

# a vocabulary large enough that the embedding bucket crosses the
# payload at which the tpu_multipod table picks pallas_fused
cfg = base.reduced(base.get_config("phi4-mini-3.8b")).replace(
    vocab_size=32768)
key = jax.random.key(0)
shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
dcfg = DataConfig(global_batch=4, seq_len=32, vocab_size=cfg.vocab_size)
tcfg = TrainConfig(backend="auto", dp_axes=("data",))

def picks(tc, mesh):
    step_fn, sh, _ = make_train_step(cfg, tc, mesh, shapes)
    return {b for d in bucket_decisions(tc, sh["bucket_plan"])
            for b in (d[0], d[2])}, step_fn, sh

# model axis 1: the step is fully manual and auto may pick the kernels
mesh1 = make_mesh((2, 1), ("data", "model"))
assert for_mesh(tcfg, mesh1) is tcfg
assert "pallas_fused" in picks(tcfg, mesh1)[0]

# model axis 2: the step leaves "model" to GSPMD, where Mosaic kernels
# cannot be partitioned -- auto runs bine there, and the step trains
mesh2 = make_mesh((2, 2), ("data", "model"))
tc2 = for_mesh(tcfg, mesh2)
got, step_fn, sh = picks(tc2, mesh2)
assert "pallas_fused" not in got and "bine" in got, got
init_p, init_s = make_init_fns(cfg, tcfg, mesh2, shapes)
with set_mesh(mesh2):
    params = init_p(key)
    state = init_s(params)
    b = make_batch(dcfg, 0)
    batch = {k: jax.device_put(v, sh["batch"][k]) for k, v in b.items()}
    assert "pallas_call" not in str(jax.make_jaxpr(step_fn)(params, state,
                                                           batch))
    params, state, metrics = step_fn(params, state, batch)
    assert np.isfinite(float(metrics["loss"]))

# an explicit pallas_fused with a model axis above 1 is refused at build
try:
    make_train_step(cfg, tcfg.replace(backend="pallas_fused"), mesh2, shapes)
except ValueError as e:
    assert "pallas_fused" in str(e)
else:
    raise AssertionError("pallas_fused with model=2 was not refused")
print("TP_AUTO_OK")
"""


def test_auto_keeps_fused_kernels_out_of_tp_step(subproc):
    out = subproc(TP_AUTO, devices=4, timeout=600)
    assert "TP_AUTO_OK" in out
