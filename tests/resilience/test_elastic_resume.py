"""Elastic resume bit-identity (8-device subprocess): lose a DP rank at
step s, resume the survivors at p' = 3 through ``elastic_train_config``,
and the continued run is BIT-identical — params and optimizer state —
to a fresh p'=3 job built from scratch and restored from the same
checkpoint.

This is the acceptance property of survivor-set rescheduling: the
elastic path is not "approximately resumed", it is exactly the run a
fresh survivor cluster would produce, because checkpoints hold global
arrays, batches are keyed by step (not by rank layout), and the ring
fallback reduces in a deterministic order.
"""

ELASTIC_RESUME_CODE = r"""
import jax, numpy as np
from jax.sharding import Mesh
from jax import set_mesh
from repro.configs import base
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig
from repro.resilience import elastic
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, make_batch
from repro.train.step import TrainConfig, make_train_step, make_init_fns

cfg = base.reduced(base.get_config("phi4-mini-3.8b"))
acfg = AdamWConfig(lr=3e-3, warmup_steps=1, total_steps=100)
key = jax.random.key(0)
params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
# global_batch divisible by BOTH 4 and 3: the batch is keyed by step, so
# survivor ranks re-slice the identical global batch
dcfg = DataConfig(global_batch=12, seq_len=64, vocab_size=cfg.vocab_size)
CKPT, S, TAIL = "/tmp/elastic_resume_ckpt", 4, 3

def build(tcfg, mesh):
    step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, params_shapes)
    init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)
    return step_fn, shardings, init_p, init_s

def advance(step_fn, shardings, params, state, start, n):
    for s in range(start, start + n):
        b = make_batch(dcfg, s)
        batch = {k: jax.device_put(v, shardings["batch"][k])
                 for k, v in b.items()}
        params, state, metrics = step_fn(params, state, batch)
        assert np.isfinite(float(metrics["loss"])), (s, metrics)
    return params, state

def restore_onto(shardings, init_p, init_s):
    params = init_p(key); state = init_s(params)
    tree, info = elastic.elastic_restore(CKPT, S,
                                         {"params": params, "state": state})
    # the int8 error-feedback buffers do not cross the config boundary
    assert info["kept_init"] == []
    assert info["dropped"] and all("'ef'" in p for p in info["dropped"])
    params = jax.device_put(tree["params"], shardings["params"])
    state = jax.device_put(tree["state"], shardings["state"])
    return params, state

# -- phase 1: the original 4-rank job (bine butterfly + int8 wire) -----------
tcfg0 = TrainConfig(backend="bine", dp_axes=("data",), adamw=acfg,
                    wire_dtype="int8")
mesh4 = Mesh(np.asarray(jax.devices()[:4]).reshape(4, 1), ("data", "model"))
step4, sh4, ip4, is4 = build(tcfg0, mesh4)
with set_mesh(mesh4):
    params, state = ip4(key), None
    state = is4(params)
    params, state = advance(step4, sh4, params, state, 0, S)
    ckpt.save(CKPT, S, {"params": params, "state": state})
assert ckpt.latest_step(CKPT) == S
print("PHASE1_OK")

# -- rank loss: 4 -> 3 (non-pow2: butterfly and int8 wire must both go) ------
plan = elastic.plan_survivors(4, [2], backend="bine", topology="lumi")
assert plan.p_new == 3 and plan.backend == "ring" and plan.fell_back
tcfgA = elastic.elastic_train_config(tcfg0, 3)
assert tcfgA.backend == "ring" and tcfgA.wire_dtype == "float32"
mesh3 = Mesh(np.asarray(jax.devices()[:3]).reshape(3, 1), ("data", "model"))

# path A: the elastic resume (adapted config, restored checkpoint)
stepA, shA, ipA, isA = build(tcfgA, mesh3)
with set_mesh(mesh3):
    pA, stA = restore_onto(shA, ipA, isA)
    pA, stA = advance(stepA, shA, pA, stA, S, TAIL)
print("PATHA_OK")

# path B: a fresh 3-rank job someone configured by hand, same checkpoint
tcfgB = TrainConfig(backend="ring", dp_axes=("data",), adamw=acfg)
stepB, shB, ipB, isB = build(tcfgB, mesh3)
with set_mesh(mesh3):
    pB, stB = restore_onto(shB, ipB, isB)
    pB, stB = advance(stepB, shB, pB, stB, S, TAIL)
print("PATHB_OK")

# bit-identity: params AND optimizer state, every leaf
for tag, a, b in (("params", pA, pB), ("state", stA, stB)):
    fa, _ = jax.tree.flatten(a)
    fb, _ = jax.tree.flatten(b)
    assert len(fa) == len(fb)
    for i, (x, y) in enumerate(zip(fa, fb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{tag} leaf {i}")
print("BIT_IDENTICAL_OK")
print("ALL_OK")
"""


def test_elastic_resume_bit_identical_8dev(subproc):
    out = subproc(ELASTIC_RESUME_CODE, devices=8, timeout=1500)
    assert "PHASE1_OK" in out
    assert "BIT_IDENTICAL_OK" in out
    assert "ALL_OK" in out
