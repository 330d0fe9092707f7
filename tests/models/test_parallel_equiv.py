"""Parallel attention strategies == single-device oracle (subprocess)."""

import pytest

CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import base
from repro.models import transformer as T, sharding as sh
from jax import set_mesh

from repro.launch.mesh import make_mesh
mesh = make_mesh((1, 1, 8), ("pod", "data", "model"))
key = jax.random.key(0)
B, S = 2, 128

def run(cfg, n_model, params, inputs):
    sh.set_model_parallel(n_model)
    if n_model == 1:
        out, _ = jax.jit(lambda p, i: T.forward(p, cfg, i))(params, inputs)
    else:
        with set_mesh(mesh):
            out, _ = jax.jit(lambda p, i: T.forward(p, cfg, i))(params, inputs)
    return np.asarray(out, np.float32)

cfgA = base.get_config("qwen3-32b").replace(
    n_layers=2, d_model=1024, n_heads=8, n_kv_heads=4, head_dim=32,
    d_ff=256, vocab_size=128, attn_chunk=32, remat=False)
params = T.init_params(key, cfgA)
inputs = jax.random.randint(key, (B, S), 0, cfgA.vocab_size)
sh.set_model_parallel(1)
ref = run(cfgA, 1, params, inputs)
sh.set_model_parallel(8)
assert sh.strategy(cfgA) == "megatron_sp"
got = run(cfgA, 8, params, inputs)
np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)

for win, lgr in ((None, 0), (16, 0), (None, 3)):
    cfgB = base.get_config("phi4-mini-3.8b").replace(
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
        d_ff=256, vocab_size=128, attn_chunk=16, remat=False, window=win,
        local_global_ratio=lgr, local_window=16)
    params = T.init_params(key, cfgB)
    inputs = jax.random.randint(key, (B, S), 0, cfgB.vocab_size)
    sh.set_model_parallel(1)
    ref = run(cfgB, 1, params, inputs)
    sh.set_model_parallel(8)
    assert sh.strategy(cfgB) == "pure_sp"
    got = run(cfgB, 8, params, inputs)
    np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)
print("ALL_OK")
"""


def test_parallel_strategies_match_oracle(subproc):
    out = subproc(CODE, devices=8, timeout=900)
    assert "ALL_OK" in out
