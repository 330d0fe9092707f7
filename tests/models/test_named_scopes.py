"""The program's named scopes reach the compiled HLO's ``op_name``
metadata, where a profiler trace finds them: the decode step's
``decode_attn``/``kv_write``, the model's ``attn``/``mlp``/``embed``/
``head``/``loss`` (forward, and the backward's ``transpose(jvp(...))``
under remat), the optimizer's ``grad_norm``/``adamw``, and each
bucket's ``rs/b<k>``/``ag/b<k>`` on four devices."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base
from repro.launch.mesh import make_mesh
from repro.models import transformer as T


def op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))


def has_scope(names, scope: str, wrapper: str = "") -> bool:
    """Some path holds ``scope`` as a component, inside ``wrapper(`` if
    given (``transpose(jvp(attn))`` holds ``attn`` inside ``transpose``)."""
    pat = re.compile(rf"(^|/|\(){re.escape(scope)}(/|\)|$)")
    return any(pat.search(n) and (not wrapper or f"{wrapper}(" in n)
               for n in names)


def _cfg(name):
    return base.reduced(base.get_config(name))


def test_decode_slots_and_insert_carry_their_scopes():
    from repro.serve.engine import ServeConfig, make_serve_fns
    cfg = _cfg("phi4-mini-3.8b")
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    B, S = 2, 32
    fns = make_serve_fns(cfg, ServeConfig(), mesh, B, S)
    with jax.set_mesh(mesh):
        params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                                jax.random.key(0))
        pool = jax.eval_shape(fns.init_pool)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        decode = op_names(fns.decode_slots.lower(
            params, pool, jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32)).compile())
        insert = op_names(fns.insert.lower(
            params, pool, jax.ShapeDtypeStruct((1, S), jnp.int32), i32,
            i32).compile())
    for scope in ("embed", "kv_write", "decode_attn", "mlp", "head"):
        assert has_scope(decode, scope), scope
    for scope in ("embed", "attn", "mlp", "head"):
        assert has_scope(insert, scope), scope
    assert not has_scope(insert, "kv_write")


def test_train_step_carries_forward_backward_and_optimizer_scopes():
    from repro.train.step import TrainConfig, make_init_fns, make_train_step
    cfg = dataclasses.replace(_cfg("qwen3-32b"), remat=True)
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    tcfg = TrainConfig(backend="xla")
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.key(0))
    step, sh, _ = make_train_step(cfg, tcfg, mesh, shapes)
    _, init_s = make_init_fns(cfg, tcfg, mesh, shapes)
    with jax.set_mesh(mesh):
        params = jax.tree.map(
            lambda s, n: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=n),
            shapes, sh["params"])
        state = jax.eval_shape(init_s, params)
        batch = {k: jax.ShapeDtypeStruct((2, 32), np.int32,
                                         sharding=sh["batch"][k])
                 for k in ("inputs", "targets")}
        names = op_names(step.lower(params, state, batch).compile())
    for scope in ("embed", "attn", "mlp", "head", "loss", "grad_norm",
                  "adamw"):
        assert has_scope(names, scope), scope
    # the backward pass keeps the forward's scopes, rematerialized too
    assert has_scope(names, "attn", wrapper="transpose")
    assert any("rematted_computation" in n and has_scope([n], "attn")
               for n in names)


_BUCKETS = r"""
import re
import jax, numpy as np
from repro.configs import base
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.train.step import TrainConfig, make_init_fns, make_train_step

cfg = base.reduced(base.get_config("phi4-mini-3.8b"))
mesh = make_mesh((4, 1), ("data", "model"))
shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))
for wire in ("float32", "int8"):
    tcfg = TrainConfig(backend="bine", wire_dtype=wire, bucket_bytes=4096)
    step, sh, _ = make_train_step(cfg, tcfg, mesh, shapes)
    assert len(sh["bucket_plan"].buckets) > 1
    _, init_s = make_init_fns(cfg, tcfg, mesh, shapes)
    with jax.set_mesh(mesh):
        params = jax.tree.map(
            lambda s, n: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=n),
            shapes, sh["params"])
        state = jax.eval_shape(init_s, params)
        batch = {k: jax.ShapeDtypeStruct((8, 32), np.int32,
                                         sharding=sh["batch"][k])
                 for k in ("inputs", "targets")}
        txt = step.lower(params, state, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', txt))
    n = len(sh["bucket_plan"].buckets)
    for k in range(n):
        for kind in ("rs", "ag"):
            assert any(f"/{kind}/b{k}/" in p for p in names), (wire, kind, k)
    # the int8 wire's error-feedback encode sits inside its bucket's RS
    if wire == "int8":
        assert any(re.search(r"/rs/b\d+/.*(round|clamp)", p) for p in names)
    print("BUCKETS", wire, n)
"""


def test_each_bucket_has_its_rs_and_ag_scope_on_four_devices(subproc):
    out = subproc(_BUCKETS, devices=4)
    assert "BUCKETS float32" in out and "BUCKETS int8" in out
