"""Miniature dry-run: lower+compile a reduced train/serve cell on an
8-device 2x2x2 mesh (the production dryrun.py does the 512-device runs).
"""

import pytest

CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import base
from repro.models import transformer as T
from repro.models.sharding import param_specs
from repro.train.step import TrainConfig, make_train_step
from repro.serve.engine import ServeConfig, make_serve_fns, cache_specs
from repro.launch import hlo as H
from jax import set_mesh

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = base.reduced(base.get_config("qwen3-32b"))
key = jax.random.key(0)
shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
tcfg = TrainConfig(backend="bine", dp_axes=("pod", "data"))
step_fn, shardings, layout = make_train_step(cfg, tcfg, mesh, shapes)

def sds(shape, dtype, sh):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

pspecs = param_specs(cfg, shapes)
params_sds = jax.tree.map(
    lambda l, s: sds(l.shape, l.dtype, NamedSharding(mesh, s)), shapes, pspecs)
from repro.launch.dryrun import _opt_shapes
state_shapes = jax.eval_shape(lambda p: _opt_shapes(cfg, tcfg, p, 4), shapes)
state_sds = jax.tree.map(lambda l, s: sds(l.shape, l.dtype, s),
                         state_shapes, shardings["state"])
B, S = 8, 64
batch_sds = {"inputs": sds((B, S), jnp.int32, shardings["batch"]["inputs"]),
             "targets": sds((B, S), jnp.int32, shardings["batch"]["targets"])}
with set_mesh(mesh):
    lowered = step_fn.lower(params_sds, state_sds, batch_sds)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
assert mem is not None
roof = H.roofline_from_compiled(compiled, 8, 4)
assert roof.flops_per_chip > 0
assert roof.coll_bytes_per_chip > 0
assert "collective-permute" in roof.coll_op_counts  # OUR bine schedules

# int8 wire cell: _opt_shapes grows the global EF rows and the step
# lowers + compiles against them
tcfg8 = TrainConfig(backend="bine", dp_axes=("pod", "data"),
                    wire_dtype="int8", bucket_bytes=-1)
step8, sh8, _ = make_train_step(cfg, tcfg8, mesh, shapes)
state8 = jax.eval_shape(lambda p: _opt_shapes(cfg, tcfg8, p, 4), shapes)
assert "ef" in state8 and all(v.dtype == jnp.float32
                              for v in state8["ef"].values())
state8_sds = jax.tree.map(lambda l, s: sds(l.shape, l.dtype, s),
                          state8, sh8["state"])
with set_mesh(mesh):
    compiled8 = step8.lower(params_sds, state8_sds, batch_sds).compile()
assert compiled8.memory_analysis() is not None

# serve: decode cell lowers too
scfg = ServeConfig(dp_axes=("pod", "data"))
prefill_fn, decode_fn, sh2 = make_serve_fns(cfg, scfg, mesh, B, 128)
state_shapes = jax.eval_shape(lambda: T.init_decode_state(cfg, B, 128))
cs = cache_specs(cfg, scfg, B, 128, mesh)
state_sds = {
  "segments": [jax.tree.map(lambda l, s: sds(l.shape, l.dtype,
                                             NamedSharding(mesh, s)), seg, sp)
               for seg, sp in zip(state_shapes["segments"], cs["segments"])],
  "pos": sds((), jnp.int32, NamedSharding(mesh, P())),
}
tok = sds((B, 1), jnp.int32, NamedSharding(mesh, P(("pod", "data"))))
with set_mesh(mesh):
    dec = decode_fn.lower(params_sds, state_sds, tok).compile()
assert dec.memory_analysis() is not None
print("ALL_OK")
"""


def test_mini_dryrun(subproc):
    out = subproc(CODE, devices=8, timeout=1500)
    assert "ALL_OK" in out
