"""Device-level obs acceptance (8 forced host devices, subprocess):

* instrumentation adds ZERO retraces to the serve pool fns (trace_counts
  identical with the registry enabled vs disabled);
* the acceptance criterion's 2-step bucketed train run records its
  per-bucket collectives once per compilation, with link-byte
  attribution equal to the ``core.traffic`` accounting for the exact
  recorded payloads.
"""


_SERVE_ZERO_RETRACE = r"""
import jax
from jax import set_mesh
from repro.configs import base as cfgbase
from repro.models import transformer as T
from repro.obs import metrics
from repro.serve.engine import ServeConfig, make_serve_fns, page_len
from repro.serve.scheduler import ContinuousBatchingScheduler, poisson_trace

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = cfgbase.reduced(cfgbase.get_config("gemma3-4b"))
S = page_len(cfg, 24, 8)

def run(enabled):
    prev = metrics.set_enabled(enabled)
    try:
        fns = make_serve_fns(cfg, ServeConfig(dp_axes=("data",),
                                              backend="auto"), mesh, 3, S)
        params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(0))
        with set_mesh(mesh):
            sched = ContinuousBatchingScheduler(cfg, fns, params, 3, S,
                                                seed=0)
            for req in poisson_trace(6, 1.0, (4, 24), 8, cfg.vocab_size,
                                     seed=0):
                sched.submit(req)
            sched.run()
        return dict(fns.trace_counts)
    finally:
        metrics.set_enabled(prev)

on = run(True)
off = run(False)
assert on == off, f"obs changed trace counts: on={on} off={off}"
for name in ("insert", "decode_slots", "evict", "init_pool"):
    assert on[name] <= 1, (name, on)
print("ZERO_RETRACE_OK", on)
"""

_TRAIN_BUCKET_REGISTRY = r"""
import jax, numpy as np
from jax import set_mesh
from repro.configs import base
from repro.core import traffic
from repro.core.schedules import get_schedule
from repro.models import transformer as T
from repro.obs import metrics
from repro.obs.collect import _wire_scale
from repro.optim.adamw import AdamWConfig
from repro.topology.cost import schedule_algo
from repro.topology.presets import get_topology
from repro.train.data import DataConfig, make_batch
from repro.train.step import (TrainConfig, bucket_decisions, make_init_fns,
                              make_train_step)

from repro.launch.mesh import make_mesh
mesh = make_mesh((8, 1), ("data", "model"))
cfg = base.reduced(base.get_config("phi4-mini-3.8b")).replace(dtype="float32")
tcfg = TrainConfig(backend="bine", topology="lumi", bucket_bytes=1 << 18,
                   adamw=AdamWConfig(lr=3e-3, warmup_steps=1,
                                     total_steps=100))
key = jax.random.key(0)
params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
reg = metrics.get_registry()
reg.reset()

step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, params_shapes)
init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)
plan = shardings["bucket_plan"]
assert plan is not None and len(plan.buckets) >= 2

dcfg = DataConfig(global_batch=8, seq_len=32, vocab_size=cfg.vocab_size)
with set_mesh(mesh):
    params = init_p(key)
    state = init_s(params)
    for s in range(2):     # the acceptance criterion's 2-step train run
        b = make_batch(dcfg, s)
        batch = {k: jax.device_put(v, shardings["batch"][k])
                 for k, v in b.items()}
        params, state, m = step_fn(params, state, batch)
    float(m["loss"])

def bucket_rows(name):
    out = {}
    for lb, v in reg.series(name):
        if lb["source"] != "train_bucket":
            continue
        k = (lb["collective"], lb["backend"], lb["wire_dtype"])
        out[k] = out.get(k, 0.0) + v
    return out

# one RS + one AG record per bucket, recorded ONCE per compilation: the
# two executed steps share one compiled step, so the counts equal the
# bucket count, not 2x it
calls = bucket_rows("collective_calls")
n_rs = sum(v for (c, _, _), v in calls.items() if c == "reduce_scatter")
n_ag = sum(v for (c, _, _), v in calls.items() if c == "allgather")
assert n_rs == n_ag == len(plan.buckets), (n_rs, n_ag, len(plan.buckets))

# link-byte attribution for the recorded dispatches == the core.traffic
# closed form at the exact recorded payloads, per (collective, backend,
# wire) — recomputed here straight from the plan
topo = get_topology("lumi", 8)
want = {}
for b, (rs_b, rs_w, ag_b, ag_w) in zip(plan.buckets,
                                       bucket_decisions(tcfg, plan)):
    for coll, backend, wire, nbytes in (
            ("reduce_scatter", rs_b, rs_w,
             int(b.nbytes(plan.wire_itemsize, 8))),
            ("allgather", ag_b, ag_w,
             int(b.nbytes(np.dtype(b.dtype).itemsize, 8)))):
        sched_coll, algo = schedule_algo(coll, backend, nbytes,
                                         tcfg.small_cutoff_bytes)
        sched = get_schedule(sched_coll, algo, 8)
        scale = _wire_scale(wire)
        glo = traffic.global_bytes(sched, 8, float(nbytes), topo) * scale
        tot = traffic.total_bytes(sched, 8, float(nbytes)) * scale
        k = (coll, backend, wire)
        loc0, glo0 = want.get(k, (0.0, 0.0))
        want[k] = (loc0 + (tot - glo), glo0 + glo)

got_loc = bucket_rows("link_local_bytes")
got_glo = bucket_rows("link_global_bytes")
assert set(want) == set(got_loc) == set(got_glo)
for k, (loc, glo) in want.items():
    assert got_loc[k] == loc, (k, got_loc[k], loc)
    assert got_glo[k] == glo, (k, got_glo[k], glo)
print("TRAIN_BUCKET_REGISTRY_OK", len(plan.buckets))
"""


def test_serve_pool_zero_retrace_with_obs(subproc):
    out = subproc(_SERVE_ZERO_RETRACE, devices=8)
    assert "ZERO_RETRACE_OK" in out


def test_train_bucket_registry_matches_traffic(subproc):
    out = subproc(_TRAIN_BUCKET_REGISTRY, devices=8)
    assert "TRAIN_BUCKET_REGISTRY_OK" in out
