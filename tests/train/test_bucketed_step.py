"""Bucketed gradient collectives vs the per-leaf path.

Two contracts, per the bucketing PR's acceptance bar:

* **fp32 bit-for-bit parity** — one optimizer step with bucketing (multi-
  bucket and table-driven) produces byte-identical params AND optimizer
  state vs ``bucket_bytes=0`` (per-leaf collectives) at p ∈ {4, 8} for
  every deterministic backend: bine, recdoub, ring, pallas_fused.  The
  ownership-preserving bucket layout is what makes this possible — see
  ``train/buckets.py``.
* **HLO structure** — the compiled step's collective-permute count drops
  from O(leaves·log p) to O(buckets·log p) (≥5× on the qwen3-32b layout
  at p=8), the fused metrics+grad-norm allreduce is exactly ONE
  all-reduce under backend="xla", and the bucketed schedule interleaves
  collectives with the fused optimizer-update ops (bucket i's update is
  independent dataflow from bucket i-1's allgather).
"""

import pytest

_PARITY = r"""
import jax, numpy as np
from repro.configs import base
from repro.models import transformer as T
from repro.train.step import TrainConfig, make_train_step, make_init_fns
from jax import set_mesh
from repro.train.data import DataConfig, make_batch
from repro.optim.adamw import AdamWConfig

MESH_SHAPE = %s
# bit-for-bit backends (ownership-preserving layout) + bine_hier, whose
# reversed-axes flat composition must scatter rows to the same ranks as
# the per-leaf sequence; xla is checked to tolerance only (psum_scatter's
# reduction order is XLA's business, not ours)
BACKENDS = ("bine", "recdoub", "ring", "pallas_fused", "bine_hier")
TOL_BACKENDS = %s
# small explicit capacity -> several buckets (the strong case) + the
# table-driven default (usually one big bucket)
BUCKET_SETTINGS = %s

from repro.launch.mesh import make_mesh
mesh = make_mesh(MESH_SHAPE, ("pod", "data", "model"))
cfg = base.reduced(base.get_config("phi4-mini-3.8b")).replace(dtype="float32")
acfg = AdamWConfig(lr=3e-3, warmup_steps=1, total_steps=100)
key = jax.random.key(0)
params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
dcfg = DataConfig(global_batch=8, seq_len=32, vocab_size=cfg.vocab_size)

def one_step(backend, bucket_bytes):
    tcfg = TrainConfig(backend=backend, dp_axes=("pod", "data"), adamw=acfg,
                       bucket_bytes=bucket_bytes)
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
                float(metrics["loss"]), float(metrics["grad_norm"]),
                shardings["bucket_plan"])

for backend in BACKENDS + TOL_BACKENDS:
    exact = backend not in TOL_BACKENDS
    ref = one_step(backend, 0)
    assert ref[4] is None                       # per-leaf: no plan
    for bb in BUCKET_SETTINGS:
        out = one_step(backend, bb)
        assert out[4] is not None, (backend, bb)
        if bb > 0:
            assert len(out[4].buckets) >= 2, (backend, bb)
        for x, y in zip(jax.tree.leaves(ref[0]) + jax.tree.leaves(ref[1]),
                        jax.tree.leaves(out[0]) + jax.tree.leaves(out[1])):
            assert x.dtype == y.dtype, (backend, bb, x.shape)
            if exact:
                assert np.array_equal(x, y), (backend, bb, x.shape)
            else:
                np.testing.assert_allclose(
                    np.asarray(x, np.float64), np.asarray(y, np.float64),
                    rtol=1e-5, atol=1e-6, err_msg=str((backend, bb, x.shape)))
        if exact:
            assert ref[2] == out[2] and ref[3] == out[3], (backend, bb)
    print(backend, "bit-for-bit OK," if exact else "allclose OK,",
          "loss", ref[2])
print("PARITY_OK")
"""


def test_bucketed_parity_p4(subproc):
    out = subproc(_PARITY % ("(2, 2, 1)", '("xla",)', "(120000, -1)"),
                  devices=8, timeout=2400)
    assert "PARITY_OK" in out


def test_bucketed_parity_p8(subproc):
    out = subproc(_PARITY % ("(2, 4, 1)", "()", "(120000,)"), devices=8,
                  timeout=2400)
    assert "PARITY_OK" in out


_HLO = r"""
import jax, numpy as np
from repro.configs import base
from repro.models import transformer as T
from repro.train.step import TrainConfig, make_train_step
from jax import set_mesh
from repro.launch import hlo, dryrun

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
cfg = base.reduced(base.get_config("qwen3-32b"))
key = jax.random.key(0)
params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
N_DP, LOGP = 8, 3

def sds(l, s):
    return jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s)

def compile_txt(backend, bb):
    tcfg = TrainConfig(backend=backend, dp_axes=("pod", "data"),
                       bucket_bytes=bb)
    step_fn, shardings, layout = make_train_step(cfg, tcfg, mesh,
                                                 params_shapes)
    state_shapes = jax.eval_shape(
        lambda p: dryrun._opt_shapes(cfg, tcfg, p, N_DP), params_shapes)
    args = (
        jax.tree.map(lambda l, s: sds(l, s), params_shapes,
                     shardings["params"]),
        jax.tree.map(lambda l, s: sds(l, s), state_shapes,
                     shardings["state"]),
        {k: sds(jax.ShapeDtypeStruct((8, 64), np.int32),
                shardings["batch"][k]) for k in ("inputs", "targets")},
    )
    with set_mesh(mesh):
        txt = step_fn.lower(*args).compile().as_text()
    return txt, shardings["bucket_plan"]

def ppermutes(txt):
    c = hlo.op_counts_from_text(txt)
    return c.get("collective-permute", 0) + c.get("collective-permute-start", 0)

layout = __import__("repro.train.zero", fromlist=["x"]).zero_layout(
    cfg, params_shapes, N_DP)
n_sharded = sum(1 for zd in jax.tree.leaves(layout) if zd >= 0)

# --- per-leaf vs bucketed (table-driven): >=5x fewer ppermutes ---
txt_leaf, plan_leaf = compile_txt("bine", 0)
assert plan_leaf is None
pp_leaf = ppermutes(txt_leaf)
assert pp_leaf == n_sharded * 2 * LOGP + LOGP, (pp_leaf, n_sharded)

txt_auto, plan_auto = compile_txt("bine", -1)
pp_auto = ppermutes(txt_auto)
assert pp_auto == len(plan_auto.buckets) * 2 * LOGP + LOGP, \
    (pp_auto, len(plan_auto.buckets))
ratio = pp_leaf / pp_auto
assert ratio >= 5.0, (pp_leaf, pp_auto, ratio)
print("ppermute per-leaf", pp_leaf, "bucketed", pp_auto, "ratio %.1f" % ratio)

# --- multi-bucket: collectives interleave with the fused updates ---
txt_mb, plan_mb = compile_txt("bine", 200000)
assert len(plan_mb.buckets) >= 2
assert ppermutes(txt_mb) == len(plan_mb.buckets) * 2 * LOGP + LOGP
seq = hlo.entry_op_sequence(txt_mb)
cp = [i for i, k in enumerate(seq) if k.startswith("collective-permute")]
fus = [i for i, k in enumerate(seq) if k == "fusion"]
inside = sum(1 for i in fus if cp[0] < i < cp[-1])
assert inside > 0, "no fused update ops between the collective chain"
print("interleave: %d fusions inside the collective span" % inside)

# --- fused metrics+grad-norm: exactly ONE all-reduce under xla ---
txt_x, _ = compile_txt("xla", -1)
cx = hlo.op_counts_from_text(txt_x)
n_ar = cx.get("all-reduce", 0) + cx.get("all-reduce-start", 0)
assert n_ar == 1, (n_ar, cx)
print("xla all-reduce count", n_ar)
print("HLO_OK")
"""


def test_bucketed_hlo_structure(subproc):
    out = subproc(_HLO, devices=8, timeout=2400)
    assert "HLO_OK" in out
