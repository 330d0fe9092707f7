"""Compile-only checks: every fused collective kernel, at real bucket
widths, for a described (not attached) TPU v5e chip.

Nothing runs; the TPU compiler refuses here what interpret mode cannot
show (tiling alignment, Mosaic lowering gaps, fast-memory limits).  Each
test asserts the kernel landed as a ``tpu_custom_call``.  The topology is
described inside a module-scoped fixture, so only the worker that runs
this file loads the TPU compiler, and the tests skip where no v5e can be
described.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.collectives import compression as comp  # noqa: E402
from repro.kernels.collectives import kernel as K  # noqa: E402

#: per-step payloads (elements), from the smallest rank block the fused
#: entries run the kernels on (``ops.block_align``: 2048 bf16 elements) up
#: to 16 MiB of f32
WIDTHS = [1 << 11, 1 << 16, 1 << 22]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    # a described chip's executables cannot be read back from the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("send", [False, True], ids=["last", "send"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h", WIDTHS)
def test_rs_step_kernel_compiles(one_chip, h, dtype, send):
    c = _sds(one_chip, (), jnp.int32)
    if send:
        fn = lambda b, r, c: K.rs_step_kernel(b, r, c, 1 - c)  # noqa: E731
    else:
        fn = lambda b, r, c: K.rs_step_kernel(b, r, c)  # noqa: E731
    txt = _compiled_text(fn, _sds(one_chip, (2 * h,), dtype),
                         _sds(one_chip, (h,), dtype), c)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("send", [False, True], ids=["last", "send"])
@pytest.mark.parametrize("h", [1 << 13, 1 << 16, 1 << 22])
def test_rs_step_kernel_q_compiles(one_chip, h, send):
    c = _sds(one_chip, (), jnp.int32)
    ch = comp.wire_chunk(h)
    if send:
        fn = lambda b, q, s, c: K.rs_step_kernel_q(b, q, s, c, 1 - c)  # noqa
    else:
        fn = lambda b, q, s, c: K.rs_step_kernel_q(b, q, s, c)  # noqa: E731
    txt = _compiled_text(fn, _sds(one_chip, (2 * h,), jnp.float32),
                         _sds(one_chip, (h,), jnp.int8),
                         _sds(one_chip, (h // ch,), jnp.float32), c)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("h", [1 << 13, 1 << 16, 1 << 22])
def test_ag_step_kernel_compiles(one_chip, h, dtype):
    c = _sds(one_chip, (), jnp.int32)
    compiled = jax.jit(lambda b, r, c: K.ag_step_kernel(b, r, c)).lower(
        _sds(one_chip, (h,), dtype), _sds(one_chip, (h,), dtype),
        c).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the (rows, 128) view is the flat vector's own layout: no relayout
    # temporaries (a narrow-minor-dim view would pad 32-64x in HBM)
    assert compiled.memory_analysis().temp_size_in_bytes < h


@pytest.mark.parametrize("mode", ["accumulate", "updated", "write"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("b", [1 << 11, 1 << 20])
def test_ring_update_kernel_compiles(one_chip, b, dtype, mode):
    kw = {"accumulate": mode != "write", "return_updated": mode == "updated"}
    txt = _compiled_text(
        lambda v, r, i: K.ring_update_kernel(v, r, i, **kw),
        _sds(one_chip, (4 * b,), dtype), _sds(one_chip, (b,), dtype),
        _sds(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kernel", ["matmul_pack", "gather_matmul"])
def test_matmul_kernels_compile(one_chip, kernel):
    """phi4-mini's MLP up-projection: [4096, 3072] @ [3072, 8192], bf16,
    four permuted row blocks."""
    fn = getattr(K, f"{kernel}_kernel")
    perm = np.asarray([2, 0, 3, 1], np.int32)
    txt = _compiled_text(lambda x, w: fn(x, w, perm),
                         _sds(one_chip, (4096, 3072), jnp.bfloat16),
                         _sds(one_chip, (3072, 8192), jnp.bfloat16))
    assert "tpu_custom_call" in txt


def test_decode_slots_keeps_the_kv_pool_in_place(one_chip, topo):
    """phi4-mini's decode step at published widths (two layers), 32 pages
    of 1024: no copy of a whole pool leaf, no dynamic-update-slice writing
    a layer's slab into a stacked output, temporaries under one leaf."""
    import dataclasses
    import re

    from repro.configs import base
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.serve.engine import ServeConfig, make_serve_fns

    cfg = dataclasses.replace(base.get_config("phi4-mini-3.8b"), n_layers=2)
    B, S = 32, 1024
    mesh = make_mesh((1, 1), ("data", "model"), topo.devices[:1])
    fns = make_serve_fns(cfg, ServeConfig(), mesh, B, S)

    def placed(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = placed(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                   jax.random.key(0)))
    pool = placed(jax.eval_shape(
        lambda: {**T.init_decode_state(cfg, B, S),
                 "pos": jnp.zeros((B,), jnp.int32)}))
    with jax.set_mesh(mesh):
        compiled = fns.decode_slots.lower(
            params, pool, _sds(one_chip, (B, 1), jnp.int32),
            _sds(one_chip, (B,), jnp.int32)).compile()
    txt = compiled.as_text()
    leaf = (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
    dims = ",".join(map(str, leaf))
    assert not re.search(rf"= bf16\[{dims}\]\S* copy\(", txt)
    assert not re.search(rf"%\S*dynamic-update-slice\S* = bf16\[{dims}\]",
                         txt)
    leaf_bytes = int(np.prod(leaf)) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes
