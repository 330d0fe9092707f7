"""shard_map collectives vs oracles on 8 forced host devices (subprocess)."""

import pytest

CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))
from repro.collectives import api, shmap
from jax import shard_map

rng = np.random.RandomState(0)
TOL = dict(rtol=1e-4, atol=1e-5)

def under(fn, in_spec=P("x"), out_spec=P("x")):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                                 out_specs=out_spec))

x = rng.randn(8, 1024).astype(np.float32)
for backend in ("bine", "recdoub", "ring", "xla"):
    cfg = api.CollectiveConfig(backend=backend, small_cutoff_bytes=0)
    out = under(lambda v: api.allreduce(v, "x", cfg))(x)
    np.testing.assert_allclose(np.asarray(out), np.tile(x.sum(0), (8, 1)), **TOL)
for backend in ("bine", "recdoub"):
    cfg = api.CollectiveConfig(backend=backend, small_cutoff_bytes=1 << 30)
    out = under(lambda v: api.allreduce(v, "x", cfg))(x)
    np.testing.assert_allclose(np.asarray(out), np.tile(x.sum(0), (8, 1)), **TOL)

# auto backend: decision-table dispatch at trace time, all topology presets
from repro.topology import PRESETS
for topo in PRESETS:
    cfg = api.CollectiveConfig(backend="auto", topology=topo)
    out = under(lambda v: api.allreduce(v, "x", cfg))(x)
    np.testing.assert_allclose(np.asarray(out), np.tile(x.sum(0), (8, 1)), **TOL)

xs = rng.randn(8, 8192).astype(np.float32)
for backend in ("bine", "recdoub", "ring", "xla", "auto"):
    out = np.asarray(under(lambda v: api.reduce_scatter(
        v.reshape(-1), "x", api.CollectiveConfig(backend=backend)))(xs))
    np.testing.assert_allclose(out.reshape(8, -1), xs.sum(0).reshape(8, -1), **TOL)

blocks = rng.randn(8, 1024).astype(np.float32)
for backend in ("bine", "recdoub", "ring", "xla", "auto"):
    out = np.asarray(under(lambda v: api.allgather(
        v.reshape(-1), "x", api.CollectiveConfig(backend=backend)))(blocks))
    np.testing.assert_allclose(out.reshape(8, -1),
                               np.tile(blocks.reshape(-1), (8, 1)), **TOL)

a = rng.randn(8, 8, 32).astype(np.float32)
for backend in ("bine", "bruck", "recdoub", "xla", "auto"):
    out = np.asarray(under(lambda v: api.all_to_all(
        v[0], "x", api.CollectiveConfig(backend=backend))[None])(a))
    np.testing.assert_allclose(out, np.transpose(a, (1, 0, 2)), **TOL)

# xla emulation dtype guard: broadcast/scatter of bool and int32 must be
# exact (the masked-psum path is float-only; these route via all_gather)
yb_ = (rng.randn(8, 64) > 0)
yi_ = rng.randint(-2**30, 2**30, (8, 64)).astype(np.int32)
cfgx = api.CollectiveConfig(backend="xla")
for arr in (yb_, yi_):
    for root in (0, 3):
        out = np.asarray(under(lambda v: api.broadcast(v, "x", root, cfgx))(arr))
        assert out.dtype == arr.dtype
        np.testing.assert_array_equal(out, np.tile(arr[root], (8, 1)))
ints = rng.randint(-2**30, 2**30, (8, 8, 32)).astype(np.int32)
sc = np.asarray(under(lambda v: api.scatter(
    v.reshape(-1), "x", 2, cfgx))(ints.reshape(8, -1))).reshape(8, -1)
np.testing.assert_array_equal(sc, ints[2])

y = rng.randn(8, 256).astype(np.float32)
for backend in ("bine", "recdoub", "xla", "auto"):
    cfg = api.CollectiveConfig(backend=backend)
    for root in (0, 3, 7):
        out = np.asarray(under(lambda v: api.broadcast(v, "x", root, cfg))(y))
        np.testing.assert_allclose(out, np.tile(y[root], (8, 1)), **TOL)
    for root in (0, 5):
        out = np.asarray(under(lambda v: api.reduce(v, "x", root, cfg))(y))
        np.testing.assert_allclose(out[root], y.sum(0), **TOL)
    for root in (0, 2, 7):
        out = np.asarray(under(lambda v: api.gather(
            v.reshape(-1), "x", root, cfg))(blocks)).reshape(8, -1)
        np.testing.assert_allclose(out[root], blocks.reshape(-1), **TOL)

xf = rng.randn(8, 8192).astype(np.float32); xf[1:] = xf[0]
for algo in ("bine", "bine_dd", "binomial"):
    out = np.asarray(under(lambda v: shmap.scatter(
        v.reshape(-1), "x", 0, algo))(xf)).reshape(8, -1)
    np.testing.assert_allclose(out, xf[0].reshape(8, -1), **TOL)

# dim-general RS/AG (the ZeRO path), over 2D leaves: w[r] = rank r's
# local contribution [64, 24]; peel the shard_map leading dim
w = rng.randn(8, 64, 24).astype(np.float32)
for dim in (0, 1):
    for algo in ("bine", "recdoub", "ring"):
        def rsf(v):
            return shmap.reduce_scatter_dim(v[0], dim, "x", algo)[None]
        out = np.asarray(under(rsf)(w))          # [8, ...shard...]
        full = w.sum(0)
        k = full.shape[dim] // 8
        for r in range(8):
            sl = [slice(None)] * 2
            sl[dim] = slice(r * k, (r + 1) * k)
            np.testing.assert_allclose(out[r], full[tuple(sl)], **TOL)
        def agf(v):
            s = shmap.reduce_scatter_dim(v[0], dim, "x", algo)
            return shmap.allgather_dim(s, dim, "x", algo)[None]
        out2 = np.asarray(under(agf)(w))
        for r in range(8):
            np.testing.assert_allclose(out2[r], full, **TOL)

# hierarchical + grad flow
from repro.launch.mesh import make_mesh
mesh2 = make_mesh((2, 4), ("pod", "data"))
xh = rng.randn(8, 512).astype(np.float32)
f = jax.jit(shard_map(
    lambda v: shmap.allreduce_hierarchical(v, "data", "pod", "bine"),
    mesh=mesh2, in_specs=P(("pod", "data")), out_specs=P(("pod", "data"))))
np.testing.assert_allclose(np.asarray(f(xh)), np.tile(xh.sum(0), (8, 1)), **TOL)

def loss(w):
    z = api.allreduce(w * w, "x",
                      api.CollectiveConfig(backend="bine", small_cutoff_bytes=0))
    return z.sum()
g = jax.jit(shard_map(jax.grad(loss), mesh=mesh, in_specs=P("x"),
                          out_specs=P("x")))
wg = rng.randn(8, 64).astype(np.float32)
np.testing.assert_allclose(np.asarray(g(wg)), 2 * wg * 8, **TOL)
print("ALL_OK")
"""


def test_all_collectives_8dev(subproc):
    out = subproc(CODE, devices=8, timeout=900)
    assert "ALL_OK" in out
