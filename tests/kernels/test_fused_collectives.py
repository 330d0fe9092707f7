"""Fused-collective kernel subsystem: kernels vs refs (single device),
emission-plan invariants, and the bit-for-bit contract vs the shmap
backend on the 8-device CPU mesh in interpret mode (subprocess)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.collectives import kernel as K  # noqa: E402
from repro.kernels.collectives import plan as fplan  # noqa: E402
from repro.kernels.collectives import ref as R  # noqa: E402

rng = np.random.RandomState(0)


# ---------------------------------------------------------------------------
# Kernels vs pure-jnp refs (interpret mode, single device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h", [8, 64, 1024, 6, 10])
def test_rs_step_kernel_matches_ref(h):
    buf = jnp.asarray(rng.randn(2 * h).astype(np.float32))
    recv = jnp.asarray(rng.randn(h).astype(np.float32))
    for c in (0, 1):
        out = K.rs_step_kernel(buf, recv, c, interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(R.rs_step_ref(buf, recv, c)))
        for cn in (0, 1):
            o, s = K.rs_step_kernel(buf, recv, c, cn, interpret=True)
            ro, rs = R.rs_step_ref(buf, recv, c, cn)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ro))
            np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.int8],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("h", [128, 512, 1 << 17])
def test_ag_step_kernel_matches_ref(h, dtype):
    buf = jnp.asarray(rng.randint(-100, 100, h), dtype)
    recv = jnp.asarray(rng.randint(-100, 100, h), dtype)
    for c in (0, 1):
        np.testing.assert_array_equal(
            np.asarray(K.ag_step_kernel(buf, recv, c, interpret=True)),
            np.asarray(R.ag_step_ref(buf, recv, c)))


def test_ring_update_kernel_matches_ref():
    v = jnp.asarray(rng.randn(96).astype(np.float32))
    recv = jnp.asarray(rng.randn(24).astype(np.float32))
    for ridx in range(4):
        got = K.ring_update_kernel(v, recv, ridx, accumulate=False,
                                   interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(R.ring_update_ref(v, recv, ridx, accumulate=False)))
        got = K.ring_update_kernel(v, recv, ridx, interpret=True)
        exp = R.ring_update_ref(v, recv, ridx)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
        got_v, upd = K.ring_update_kernel(v, recv, ridx, return_updated=True,
                                          interpret=True)
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(exp))
        # the second output is the updated block == the next ring send
        np.testing.assert_array_equal(
            np.asarray(upd), np.asarray(exp)[ridx * 24:(ridx + 1) * 24])


@pytest.mark.parametrize("m,k,n,p", [(32, 16, 24, 4), (64, 32, 64, 8),
                                     (16, 8, 8, 2)])
def test_matmul_kernels_match_refs(m, k, n, p):
    x = jnp.asarray(rng.randn(m, k).astype(np.float32))
    w = jnp.asarray(rng.randn(k, n).astype(np.float32))
    perm = np.asarray(rng.permutation(p), np.int32)
    np.testing.assert_allclose(
        np.asarray(K.matmul_pack_kernel(x, w, jnp.asarray(perm),
                                        interpret=True)),
        np.asarray(R.matmul_pack_ref(x, w, perm)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(K.gather_matmul_kernel(x, w, jnp.asarray(perm),
                                          interpret=True)),
        np.asarray(R.gather_matmul_ref(x, w, perm)), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Emission plans: the dry-run claim — fewer ops, no more bytes, same wire
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("collective", fplan.COLLECTIVES)
@pytest.mark.parametrize("algo", fplan.ALGOS)
@pytest.mark.parametrize("p", [4, 8, 16])
def test_fused_plan_dominates(collective, algo, p):
    for nelems in (p * 64, 65536):
        unfused, fused = fplan.path_plans(collective, algo, p, nelems)
        assert fused.ops < unfused.ops
        assert fused.hbm_bytes <= unfused.hbm_bytes
        # the wire side is path-invariant by construction
        assert fused.ppermute_ops == unfused.ppermute_ops
        assert fused.wire_bytes == unfused.wire_bytes


def test_plan_rejects_unknown():
    with pytest.raises(ValueError, match="collective"):
        fplan.path_plans("broadcast", "bine", 8, 512)
    with pytest.raises(ValueError, match="algo"):
        fplan.path_plans("allreduce", "bruck", 8, 512)


# ---------------------------------------------------------------------------
# 8-device mesh: bit-for-bit vs the shmap backend, all schedule families
# ---------------------------------------------------------------------------

CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))
from repro.collectives import api, shmap
from jax import shard_map
from repro.kernels import collectives as fused

rng = np.random.RandomState(0)

# interpreted kernels need check_vma=False (ops.default_interpret)
def under(fn, in_spec=P("x"), out_spec=P("x"), m=mesh):
    return jax.jit(shard_map(fn, mesh=m, in_specs=in_spec, out_specs=out_spec,
                             check_vma=False))

def kernels_in(fn, arg):
    return "pallas_call" in str(jax.make_jaxpr(under(fn))(arg))

# rank blocks of whole tiles (1024 f32) run the kernels; unaligned ones
# (256) run the shmap schedule -- both bit-identical to the shmap backend
x = rng.randn(8, 8192).astype(np.float32)
blocks = rng.randn(8, 1024).astype(np.float32)
x_un = rng.randn(8, 2048).astype(np.float32)
blocks_un = rng.randn(8, 256).astype(np.float32)
for algo in fused.ALGOS:
    cfg = api.CollectiveConfig(backend="pallas_fused", fused_algo=algo,
                               small_cutoff_bytes=0)
    ref = api.CollectiveConfig(backend=algo, small_cutoff_bytes=0)
    for name, fn, arg, arg_un in (
        ("allreduce", lambda v, c: api.allreduce(v, "x", c), x, x_un),
        ("reduce_scatter",
         lambda v, c: api.reduce_scatter(v.reshape(-1), "x", c), x, x_un),
        ("allgather",
         lambda v, c: api.allgather(v.reshape(-1), "x", c), blocks,
         blocks_un),
    ):
        for aligned, a_in in ((True, arg), (False, arg_un)):
            assert kernels_in(lambda v: fn(v, cfg), a_in) == aligned, \
                (name, algo, aligned)
            a = np.asarray(under(lambda v: fn(v, cfg))(a_in))
            b = np.asarray(under(lambda v: fn(v, ref))(a_in))
            np.testing.assert_array_equal(a, b), (name, algo)

# small-allreduce regime parity (fused falls back to the shmap small path)
cfg_small = api.CollectiveConfig(backend="pallas_fused",
                                 small_cutoff_bytes=1 << 30)
ref_small = api.CollectiveConfig(backend="bine", small_cutoff_bytes=1 << 30)
a = np.asarray(under(lambda v: api.allreduce(v, "x", cfg_small))(x))
b = np.asarray(under(lambda v: api.allreduce(v, "x", ref_small))(x))
np.testing.assert_array_equal(a, b)

# tuple-axis case: the flattened ("pod","data") gradient axis
from repro.launch.mesh import make_mesh
mesh2 = make_mesh((2, 4), ("pod", "data"))
xh = rng.randn(8, 8192).astype(np.float32)
for algo in fused.ALGOS:
    cfg = api.CollectiveConfig(backend="pallas_fused", fused_algo=algo,
                               small_cutoff_bytes=0)
    ref = api.CollectiveConfig(backend=algo, small_cutoff_bytes=0)
    ax = ("pod", "data")
    a = np.asarray(under(lambda v: api.allreduce(v, ax, cfg),
                         P(ax), P(ax), mesh2)(xh))
    b = np.asarray(under(lambda v: api.allreduce(v, ax, ref),
                         P(ax), P(ax), mesh2)(xh))
    np.testing.assert_array_equal(a, b)

# rooted fallbacks through the pallas_fused dispatch: non-root correctness
y = rng.randn(8, 128).astype(np.float32)
cfgf = api.CollectiveConfig(backend="pallas_fused")
for root in (0, 3, 7):
    out = np.asarray(under(lambda v: api.broadcast(v, "x", root, cfgf))(y))
    np.testing.assert_allclose(out, np.tile(y[root], (8, 1)), rtol=1e-5)
out = np.asarray(under(lambda v: api.gather(
    v.reshape(-1), "x", 5, cfgf))(blocks)).reshape(8, -1)
np.testing.assert_allclose(out[5], blocks.reshape(-1), rtol=1e-5)

# dim-general fused RS/AG (the train-step ZeRO path)
w = rng.randn(8, 64, 128).astype(np.float32)
for dim in (0, 1):
    for algo in fused.ALGOS:
        full = w.sum(0)
        out = np.asarray(under(
            lambda v: fused.reduce_scatter_dim(v[0], dim, "x", algo)[None])(w))
        k = full.shape[dim] // 8
        for r in range(8):
            sl = [slice(None)] * 2
            sl[dim] = slice(r * k, (r + 1) * k)
            np.testing.assert_allclose(out[r], full[tuple(sl)],
                                       rtol=1e-5, atol=1e-5)
        rt = np.asarray(under(lambda v: fused.allgather_dim(
            fused.reduce_scatter_dim(v[0], dim, "x", algo),
            dim, "x", algo)[None])(w))
        for r in range(8):
            np.testing.assert_allclose(rt[r], full, rtol=1e-5, atol=1e-5)

# fused matmul+RS and AG+matmul vs unfused compositions
xm = rng.randn(8, 64, 32).astype(np.float32)
wm = jnp.asarray(rng.randn(32, 128).astype(np.float32))
ysum = np.einsum("rmk,kn->mn", xm, np.asarray(wm))
xb = rng.randn(8, 32, 32).astype(np.float32)
fullg = xb.reshape(256, 32) @ np.asarray(wm)
for algo in fused.ALGOS:
    got = np.asarray(under(
        lambda v: fused.matmul_reduce_scatter(v[0], wm, "x", algo)[None])(xm))
    for r in range(8):
        np.testing.assert_allclose(got[r], ysum[r * 8:(r + 1) * 8],
                                   rtol=1e-4, atol=1e-4)
    got = np.asarray(under(
        lambda v: fused.allgather_matmul(v[0], wm, "x", algo)[None])(xb))
    for r in range(8):
        np.testing.assert_allclose(got[r], fullg, rtol=1e-4, atol=1e-4)

# backend="auto" may resolve to pallas_fused from the rebuilt tables and
# must execute correctly when it does
cfga = api.CollectiveConfig(backend="auto", topology="tpu_multipod")
out = np.asarray(under(lambda v: api.allreduce(v, "x", cfga))(x))
np.testing.assert_allclose(out, np.tile(x.sum(0), (8, 1)),
                           rtol=1e-4, atol=1e-5)
print("FUSED_OK")
"""


def test_fused_backend_8dev_bitwise(subproc):
    out = subproc(CODE, devices=8, timeout=1200)
    assert "FUSED_OK" in out


# ---------------------------------------------------------------------------
# int8 wire codec: step kernel vs oracle, fused vs shmap bit parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h", [512, 1024, 2048])
def test_rs_step_kernel_q_matches_ref(h):
    """Codec RS step kernel vs ``ref.rs_step_ref_q``, bit for bit: the
    dequantize+accumulate pass and the fused re-quantize of the next
    outgoing half (pow2 scales make both sides exact in f32)."""
    from repro.collectives import compression as comp
    buf = jnp.asarray((rng.randn(2 * h) * 3).astype(np.float32))
    recv = jnp.asarray((rng.randn(h) * 3).astype(np.float32))
    rq, rs_ = comp.quantize_wire(recv)
    for c in (0, 1):
        np.testing.assert_array_equal(
            np.asarray(K.rs_step_kernel_q(buf, rq, rs_, c, interpret=True)),
            np.asarray(R.rs_step_ref_q(buf, rq, rs_, c)))
        for cn in (0, 1):
            o, q, s = K.rs_step_kernel_q(buf, rq, rs_, c, cn, interpret=True)
            ro, rq2, rs2 = R.rs_step_ref_q(buf, rq, rs_, c, cn)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ro))
            np.testing.assert_array_equal(np.asarray(q), np.asarray(rq2))
            np.testing.assert_array_equal(np.asarray(s), np.asarray(rs2))


def test_rs_step_kernel_q_nosend_small():
    """The no-send variant has no 512-alignment requirement."""
    from repro.collectives import compression as comp
    h = 256
    buf = jnp.asarray(rng.randn(2 * h).astype(np.float32))
    rq, rs_ = comp.quantize_wire(jnp.asarray(rng.randn(h).astype(np.float32)))
    for c in (0, 1):
        np.testing.assert_array_equal(
            np.asarray(K.rs_step_kernel_q(buf, rq, rs_, c, interpret=True)),
            np.asarray(R.rs_step_ref_q(buf, rq, rs_, c)))


QWIRE_CODE = r"""
import math
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.collectives import api, shmap
from repro.collectives import compression as comp
from jax import shard_map
from repro.kernels import collectives as fused

rng = np.random.RandomState(0)
devs = jax.devices()

def under(fn, p, in_spec=P("x"), out_spec=P("x")):
    m = Mesh(np.asarray(devs[:p]), ("x",))
    return jax.jit(shard_map(fn, mesh=m, in_specs=in_spec, out_specs=out_spec,
                             check_vma=False))

for p in (4, 8):
    for algo in ("bine", "recdoub"):
        # rank blocks of whole int8 kernel tiles (8192 elements)
        x = (rng.randn(p, p * 8192) * 3).astype(np.float32)
        blocks = (rng.randn(p, 8192) * 3).astype(np.float32)
        assert "pallas_call" in str(jax.make_jaxpr(under(
            lambda v: fused.reduce_scatter_q(v.reshape(-1), "x", algo),
            p))(x))

        # --- reduce_scatter: fused vs shmap must decode bit-identically
        a = np.asarray(under(
            lambda v: fused.reduce_scatter_q(v.reshape(-1), "x", algo), p)(x))
        b = np.asarray(under(
            lambda v: shmap.reduce_scatter_q(v.reshape(-1), "x", algo), p)(x))
        np.testing.assert_array_equal(a, b), ("rs", p, algo)

        # ...and land within the accumulated per-step quantization bound
        full = x.sum(0).reshape(p, -1)
        atol = 4.0 * np.abs(x).sum(0).max() / 127.0 * math.log2(p)
        np.testing.assert_allclose(a.reshape(p, -1), full, atol=atol)

        # --- allgather: fused vs shmap bit-identical, all ranks agree
        a = np.asarray(under(
            lambda v: fused.allgather_q(v.reshape(-1), "x", algo), p)(blocks))
        b = np.asarray(under(
            lambda v: shmap.allgather_q(v.reshape(-1), "x", algo), p)(blocks))
        np.testing.assert_array_equal(a, b), ("ag", p, algo)
        g = a.reshape(p, -1)
        for r in range(1, p):
            np.testing.assert_array_equal(g[0], g[r])
        np.testing.assert_allclose(
            g[0], blocks.reshape(-1),
            atol=np.abs(blocks).max() / 127.0 + 1e-7)

        # --- unaligned per-rank block (blk % 8192 != 0): the fused entry
        # falls back to the shmap codec path -- still bit-identical
        xr = (rng.randn(p, p * 192) * 3).astype(np.float32)
        a = np.asarray(under(
            lambda v: fused.reduce_scatter_q(v.reshape(-1), "x", algo), p)(xr))
        b = np.asarray(under(
            lambda v: shmap.reduce_scatter_q(v.reshape(-1), "x", algo), p)(xr))
        np.testing.assert_array_equal(a, b), ("rs-ragged", p, algo)

# --- api dispatch: wire_dtype="int8" routes pallas_fused and bine to the
# same bits; ring-family fused_algo and non-pow2 axes pass through to f32
x8 = (rng.randn(8, 8 * 8192) * 3).astype(np.float32)
cfg_f = api.CollectiveConfig(backend="pallas_fused", fused_algo="bine",
                             small_cutoff_bytes=0, wire_dtype="int8")
cfg_s = api.CollectiveConfig(backend="bine", small_cutoff_bytes=0,
                             wire_dtype="int8")
a = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg_f), 8)(x8))
b = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg_s), 8)(x8))
np.testing.assert_array_equal(a, b)

cfg_ring = api.CollectiveConfig(backend="pallas_fused", fused_algo="ring",
                                small_cutoff_bytes=0, wire_dtype="int8")
cfg_ring_f32 = api.CollectiveConfig(backend="pallas_fused",
                                    fused_algo="ring", small_cutoff_bytes=0)
a = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg_ring), 8)(x8))
b = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg_ring_f32), 8)(x8))
np.testing.assert_array_equal(a, b)

# non-pow2 axis (p=6): the adapter schedules have no codec variant, so an
# int8 wire silently runs the plain float32 path -- identical bits (the
# ring family is the live non-pow2 plain path)
x6 = (rng.randn(6, 6 * 1024) * 3).astype(np.float32)
cfg6 = api.CollectiveConfig(backend="pallas_fused", fused_algo="ring",
                            small_cutoff_bytes=0, wire_dtype="int8")
cfg6_f32 = api.CollectiveConfig(backend="pallas_fused", fused_algo="ring",
                                small_cutoff_bytes=0)
a = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg6), 6)(x6))
b = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg6_f32), 6)(x6))
np.testing.assert_array_equal(a, b)
bl6 = (rng.randn(6, 1024) * 3).astype(np.float32)
a = np.asarray(under(
    lambda v: api.allgather(v.reshape(-1), "x", cfg6), 6)(bl6))
b = np.asarray(under(
    lambda v: api.allgather(v.reshape(-1), "x", cfg6_f32), 6)(bl6))
np.testing.assert_array_equal(a, b)

# wire_dtype="auto" on a non-codec backend snaps to float32 and matches
# the plain path bit for bit, non-pow2 axis included
cfg_auto = api.CollectiveConfig(backend="ring", small_cutoff_bytes=0,
                                wire_dtype="auto", topology="lumi")
cfg_ring_plain = api.CollectiveConfig(backend="ring", small_cutoff_bytes=0)
a = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg_auto), 6)(x6))
b = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg_ring_plain), 6)(x6))
np.testing.assert_array_equal(a, b)

# bfloat16 wire rides the dtype-generic path and comes back f32
cfg_bf = api.CollectiveConfig(backend="bine", small_cutoff_bytes=0,
                              wire_dtype="bfloat16")
a = np.asarray(under(
    lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg_bf), 8)(x8))
assert a.dtype == np.float32 and a.size == x8.shape[1]
np.testing.assert_allclose(a, x8.sum(0), rtol=0.05, atol=0.2)

print("QWIRE_OK")
"""


def test_int8_wire_fused_vs_shmap_bitwise(subproc):
    """The satellite conformance rows: int8-wire RS/AG fused-vs-shmap bit
    parity at p in {4, 8} (both butterfly families), the unaligned and
    non-pow2 pass-throughs, and the api-level wire dispatch."""
    out = subproc(QWIRE_CODE, devices=8, timeout=1200)
    assert "QWIRE_OK" in out
