"""Conformance matrix: every (collective, algo) pair in the schedule
registry executes correctly on the numpy oracle at p in
{4, 6, 8, 12, 16, 24} — power-of-two AND non-power-of-two rank counts.

The paper's flat constructions are defined for p = 2**s; the schedule IR's
non-pow2 adapters (proxy-rank folding / 3-2 elimination, see
``core.schedules``) extend every registered pair to arbitrary p, so the
old "ring passes or ``log2_int`` raises" escape hatch is gone: anything in
the registry must pass the oracle at every p here.  New algorithms added
to the registry are picked up automatically via ``list_algos``.
"""

import pytest

from repro.core import simulate
from repro.core.schedules import COLLECTIVES, get_schedule, list_algos

PS = (4, 6, 8, 12, 16, 24)

#: rooted collectives: re-check at a nonzero root (the paper's rotation)
ROOTED = ("broadcast", "reduce", "gather", "scatter")

MATRIX = [(c, a, p) for c in COLLECTIVES for a in list_algos(c) for p in PS]


@pytest.mark.parametrize("collective,algo,p", MATRIX,
                         ids=[f"{c}-{a}-p{p}" for c, a, p in MATRIX])
def test_schedule_conformance(collective, algo, p):
    simulate.check(collective, algo, p)


@pytest.mark.parametrize(
    "collective,algo", [(c, a) for c in ROOTED for a in list_algos(c)],
    ids=[f"{c}-{a}" for c in ROOTED for a in list_algos(c)])
@pytest.mark.parametrize("p", PS)
def test_rooted_nonzero_roots(collective, algo, p):
    """Root rotation (Sec. 2.2): correctness at every root class,
    including non-pow2 p where the rotation relabels adapter proxies."""
    for root in (1, p // 2, p - 1):
        simulate.check(collective, algo, p, root=root)


def test_registry_covers_every_collective():
    for c in COLLECTIVES:
        assert list_algos(c), f"no algorithms registered for {c}"
        # and the registry's names really build (p=4 spot check)
        for a in list_algos(c):
            assert get_schedule(c, a, 4), (c, a)


# ---------------------------------------------------------------------------
# pallas_fused dispatch leg: every API collective executes through
# backend="pallas_fused" at p in {4, 8} and matches the oracle — the
# kernel-backed trio (allreduce/RS/AG) for every fused schedule family,
# the rooted collectives + alltoall through the documented shmap fallback
# (non-root cases included).
# ---------------------------------------------------------------------------

_FUSED_DISPATCH = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.collectives import api
from jax import shard_map

rng = np.random.RandomState(0)

for p in (4, 8):
    mesh = Mesh(np.asarray(jax.devices()[:p]), ("x",))
    # interpreted kernels need check_vma=False (ops.default_interpret)
    def under(fn):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x"), check_vma=False))

    x = rng.randn(p, 512).astype(np.float32)
    blocks = rng.randn(p, 64).astype(np.float32)
    # rank blocks of whole kernel tiles (1024 f32), so the kernels run
    xk = rng.randn(p, p * 1024).astype(np.float32)
    bk = rng.randn(p, 1024).astype(np.float32)
    for algo in ("bine", "recdoub", "ring"):
        cfg = api.CollectiveConfig(backend="pallas_fused", fused_algo=algo,
                                   small_cutoff_bytes=0)
        out = np.asarray(under(lambda v: api.allreduce(v, "x", cfg))(xk))
        np.testing.assert_allclose(out, np.tile(xk.sum(0), (p, 1)),
                                   rtol=1e-4, atol=1e-5)
        out = np.asarray(under(
            lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg))(xk))
        np.testing.assert_allclose(out.reshape(p, -1),
                                   xk.sum(0).reshape(p, -1),
                                   rtol=1e-4, atol=1e-5)
        out = np.asarray(under(
            lambda v: api.allgather(v.reshape(-1), "x", cfg))(bk))
        np.testing.assert_allclose(
            out.reshape(p, -1), np.tile(bk.reshape(-1), (p, 1)),
            rtol=1e-4, atol=1e-5)

    # fallback family: rooted + alltoall through the pallas_fused dispatch
    for algo in ("bine", "recdoub"):
        cfg = api.CollectiveConfig(backend="pallas_fused", fused_algo=algo)
        for root in (0, p - 1):
            out = np.asarray(under(
                lambda v: api.broadcast(v, "x", root, cfg))(x))
            np.testing.assert_allclose(out, np.tile(x[root], (p, 1)),
                                       rtol=1e-5)
            out = np.asarray(under(
                lambda v: api.reduce(v, "x", root, cfg))(x))
            np.testing.assert_allclose(out[root], x.sum(0), rtol=1e-4,
                                       atol=1e-5)
            out = np.asarray(under(lambda v: api.gather(
                v.reshape(-1), "x", root, cfg))(blocks)).reshape(p, -1)
            np.testing.assert_allclose(out[root], blocks.reshape(-1),
                                       rtol=1e-5)
            out = np.asarray(under(lambda v: api.scatter(
                v.reshape(-1), "x", root, cfg))(
                    np.tile(x[:1], (p, 1)))).reshape(p, -1)
            np.testing.assert_allclose(out.reshape(-1), x[0], rtol=1e-5)
    a2a = rng.randn(p, p, 16).astype(np.float32)
    cfg = api.CollectiveConfig(backend="pallas_fused")
    out = np.asarray(under(lambda v: api.all_to_all(v[0], "x", cfg)[None])(a2a))
    np.testing.assert_allclose(out, np.transpose(a2a, (1, 0, 2)), rtol=1e-5)
print("FUSED_DISPATCH_OK")
"""


def test_pallas_fused_dispatch_matrix(subproc):
    out = subproc(_FUSED_DISPATCH, devices=8, timeout=1200)
    assert "FUSED_DISPATCH_OK" in out


# ---------------------------------------------------------------------------
# Bucketed-dispatch row: backend="auto" + gradient bucketing must resolve
# every bucket to a valid CANDIDATES entry at the BUCKET's byte size (the
# whole point of packing: the selector prices large uniform payloads, not
# per-leaf crumbs) — for every shipped topology table.
# ---------------------------------------------------------------------------

def test_bucketed_auto_dispatch_all_tables():
    import jax
    import numpy as np

    from repro.configs import base
    from repro.models import transformer as T
    from repro.topology import CANDIDATES, PRESETS, select_backend
    from repro.train import zero
    from repro.train.step import (TrainConfig, bucket_backends,
                                  resolve_bucket_plan)

    n_dp = 8
    cfg = base.reduced(base.get_config("qwen3-32b"))
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.key(0))
    layout = zero.zero_layout(cfg, shapes, n_dp)
    for name in PRESETS:
        tcfg = TrainConfig(backend="auto", topology=name,
                           bucket_bytes=200_000)
        plan = resolve_bucket_plan(tcfg, n_dp, shapes, layout)
        assert plan is not None and len(plan.buckets) >= 2, name
        for bucket, (rs, ag) in zip(plan.buckets,
                                    bucket_backends(tcfg, plan)):
            assert rs in CANDIDATES["reduce_scatter"], (name, rs)
            assert ag in CANDIDATES["allgather"], (name, ag)
            # resolved at the bucket's (not a leaf's) byte size
            rs_bytes = bucket.nbytes(plan.wire_itemsize, n_dp)
            ag_bytes = bucket.nbytes(np.dtype(bucket.dtype).itemsize, n_dp)
            assert rs == select_backend("reduce_scatter", n_dp, rs_bytes,
                                        name)
            assert ag == select_backend("allgather", n_dp, ag_bytes, name)
            for s in bucket.slots:
                assert rs_bytes >= s.size * plan.wire_itemsize
        # table-driven capacity resolves too (bucket_bytes=-1)
        plan2 = resolve_bucket_plan(
            TrainConfig(backend="auto", topology=name), n_dp, shapes, layout)
        assert plan2 is not None
        for rs, ag in bucket_backends(
                TrainConfig(backend="auto", topology=name), plan2):
            assert rs in CANDIDATES["reduce_scatter"]
            assert ag in CANDIDATES["allgather"]
