"""End-to-end CPU autotune: probe -> measured table -> measured dispatch.

Acceptance contract (c): `launch/tune.py --grid tiny` on forced host
devices, then a train step built with ``tuning="measured"`` must resolve
its bucket collectives from the measured table — asserted through the
dryrun bucket-plan report (``train.step.bucket_report``) and by
lowering + compiling the step with that dispatch.

One subprocess, real timings, real pallas-interpret cells: the slowest
test in the suite, and the one that proves the whole measurement plane
hangs together.
"""

CODE = r"""
import os, tempfile
tmp = tempfile.mkdtemp()
os.environ["REPRO_MEASURE_DIR"] = os.path.join(tmp, "measurements")
os.environ["REPRO_MEASURED_TABLE_DIR"] = os.path.join(tmp, "tables")

# ---- 1. probe the tiny grid + write the measured table (the CLI) ----
from repro.launch import tune
assert tune.main(["--grid", "tiny", "--topology", "tpu_multipod",
                  "--timestamp", "e2e"]) == 0

from repro.topology import load_table, measured_table_path
assert os.path.exists(measured_table_path("tpu_multipod"))
table = load_table("tpu_multipod", tuning="measured")
n_meas = table.measured_cell_count()
assert n_meas == 9, n_meas   # 3 collectives x 3 tiny-grid size buckets

# the measurement store carries provenance
from repro.tuner import load_all_measurements
sets = load_all_measurements(topology="tpu_multipod")
assert len(sets) == 1 and sets[0].provenance["grid"] == "tiny"
assert sets[0].provenance["timestamp"] == "e2e"
# 3 colls x 5 candidates x 3 sizes = 45 float32 cells, plus the codec
# pairs on RS/AG: 2 colls x (3 codec backends x 2 wires) x 3 sizes = 36
assert len(sets[0].measurements) == 81   # 45 float32 + 36 codec
assert all(m.time_s > 0 for m in sets[0].measurements)

# ---- 2. a measured-tuning train step dispatches from that table ----
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import base
from repro.models import transformer as T
from repro.models.sharding import param_specs
from repro.train.step import TrainConfig, make_train_step, bucket_report
from repro.launch.dryrun import _opt_shapes
from jax import set_mesh

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 1), ("data", "model"))
cfg = base.reduced(base.get_config("qwen3-32b"))
shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))
tcfg = TrainConfig(backend="auto", tuning="measured", dp_axes=("data",),
                   bucket_bytes=1 << 20)
step_fn, shardings, layout = make_train_step(cfg, tcfg, mesh, shapes)
plan = shardings["bucket_plan"]
assert plan is not None and plan.buckets

report = bucket_report(tcfg, plan)
assert report, "empty bucket-plan report"
measured_rows = [r for r in report if r["rs_provenance"] == "measured"]
assert measured_rows, f"no bucket hit a measured cell: {report}"
for r in report:
    # the report's backend IS the measured table's decision at the
    # bucket's payload — the dispatch the step traced with
    assert r["rs_backend"] == table.lookup("reduce_scatter", 4,
                                           r["rs_bytes"]), r
    assert r["ag_backend"] == table.lookup("allgather", 4, r["ag_bytes"]), r
    assert r["rs_provenance"] in ("measured", "analytic")

# analytic tuning on the same step must NOT claim measured provenance
rep_analytic = bucket_report(tcfg.replace(tuning="analytic"), plan)
assert all(r["rs_provenance"] == "analytic" for r in rep_analytic)
# a pinned backend reports fixed provenance
rep_fixed = bucket_report(tcfg.replace(backend="bine"), plan)
assert all(r["rs_provenance"] == "fixed" for r in rep_fixed)

# ---- 3. the step lowers + compiles with the measured dispatch ----
def sds(l, s):
    return jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s)
pspecs = param_specs(cfg, shapes)
params_sds = jax.tree.map(
    lambda l, s: sds(l, NamedSharding(mesh, s)), shapes, pspecs)
state_shapes = jax.eval_shape(lambda p: _opt_shapes(cfg, tcfg, p, 4), shapes)
state_sds = jax.tree.map(lambda l, s: sds(l, s), state_shapes,
                         shardings["state"])
B, S = 8, 64
batch_sds = {
  "inputs": sds(jax.ShapeDtypeStruct((B, S), jnp.int32),
                shardings["batch"]["inputs"]),
  "targets": sds(jax.ShapeDtypeStruct((B, S), jnp.int32),
                 shardings["batch"]["targets"])}
with set_mesh(mesh):
    compiled = step_fn.lower(params_sds, state_sds, batch_sds).compile()
assert compiled is not None
print("TUNE_E2E_OK", n_meas, len(measured_rows), "of", len(report))
"""


def test_tune_measured_dispatch_e2e(subproc):
    out = subproc(CODE, devices=4, timeout=900)
    assert "TUNE_E2E_OK" in out
