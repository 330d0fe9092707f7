"""Fused-kernel dry-run + microbench: pallas_fused vs the unfused shmap path.

Three layers, all recorded into ``BENCH_collectives.json`` via the shared
:class:`benchmarks.common.Recorder`:

  1. **Emission plans** (``repro.kernels.collectives.plan``): per
     (collective, algo, p), the HLO-level ops and HBM bytes each path
     emits.  The fused path must emit FEWER ops and NO MORE bytes for the
     same schedule — asserted here, per the acceptance bar.
  2. **HLO validation** (8 host devices, subprocess): both paths are
     compiled and parsed with ``launch.hlo``; the collective-permute
     count of each real module must equal the plan's ``ppermute_ops`` —
     same wire structure, only the local lowering differs.  (The fused
     path's *local* CPU ops are the Pallas interpreter's emulation and
     are NOT compared against the plan; the plan's fused numbers model
     the TPU lowering, one custom-call per step kernel.)
  3. **Microbench**: CPU wall time per call for both paths.  Interpret-
     mode Pallas is an emulation — the CPU timing is a sanity signal
     (the schedules execute), never the performance claim; the roofline
     layers above are.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from repro.kernels.collectives import plan as fplan

P_LIST = (4, 8)
NELEMS = 8192

SNIPPET = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.collectives import api, shmap
from jax import shard_map
from repro.kernels import collectives as fused
from repro.launch import hlo
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))

NELEMS = %d
rng = np.random.RandomState(0)
x = rng.randn(8, NELEMS).astype(np.float32)
blocks = rng.randn(8, NELEMS // 8).astype(np.float32)
out = []

def build(coll, algo, backend):
    cfg = api.CollectiveConfig(backend=backend, fused_algo=algo,
                               small_cutoff_bytes=0)
    if backend != "pallas_fused":
        cfg = cfg.replace(backend=algo)
    if coll == "allreduce":
        fn, arg = (lambda v: api.allreduce(v, "x", cfg)), x
    elif coll == "reduce_scatter":
        fn, arg = (lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg)), x
    else:
        fn, arg = (lambda v: api.allgather(v.reshape(-1), "x", cfg)), blocks
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("x"),
                             out_specs=P("x"), check_vma=False)), arg

for coll in ("allreduce", "reduce_scatter", "allgather"):
    for algo in ("bine", "recdoub", "ring"):
        f_ref, arg = build(coll, algo, "shmap")
        f_fused, _ = build(coll, algo, "pallas_fused")
        a = np.asarray(f_ref(arg)); b = np.asarray(f_fused(arg))
        np.testing.assert_array_equal(a, b)   # bit-for-bit (fp32)
        rec = {"collective": coll, "algo": algo}
        for name, f in (("shmap", f_ref), ("pallas_fused", f_fused)):
            txt = f.lower(arg).compile().as_text()
            counts = hlo.op_counts_from_text(txt)
            rec[name + "_ppermute_ops"] = counts.get("collective-permute",
                counts.get("collective-permute-start", 0))
            t0 = time.perf_counter()
            for _ in range(5):
                r = f(arg)
            jax.block_until_ready(r)
            rec[name + "_us"] = (time.perf_counter() - t0) / 5 * 1e6
        out.append(rec)
print("JSON:" + json.dumps(out))
"""


def _subprocess_records():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SNIPPET % NELEMS)],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    line = next(l for l in proc.stdout.splitlines() if l.startswith("JSON:"))
    return json.loads(line[len("JSON:"):])


def run(recorder=None):
    # ---- layer 1: emission plans (the dry-run comparison) ----
    print("collective,algo,p,unfused_ops,fused_ops,unfused_hbm_bytes,"
          "fused_hbm_bytes")
    for coll in fplan.COLLECTIVES:
        for algo in fplan.ALGOS:
            for p in P_LIST:
                cmp = fplan.compare(coll, algo, p, NELEMS)
                u, f = cmp["unfused"], cmp["fused"]
                assert f["ops"] < u["ops"], cmp
                assert f["hbm_bytes"] <= u["hbm_bytes"], cmp
                print(f"{coll},{algo},{p},{u['ops']},{f['ops']},"
                      f"{u['hbm_bytes']:.0f},{f['hbm_bytes']:.0f}")
                if recorder is not None:
                    cfg = {"collective": coll, "algo": algo, "p": p,
                           "nelems": NELEMS}
                    for side in ("unfused", "fused"):
                        for metric in ("ops", "hbm_bytes"):
                            recorder.add("fused_collectives_plan", cfg,
                                         f"{side}_{metric}",
                                         cmp[side][metric])

    # ---- layers 2+3: real HLO wire validation + CPU microbench ----
    recs = _subprocess_records()
    print("collective,algo,shmap_ppermutes,fused_ppermutes,shmap_us,"
          "fused_us_interpret")
    for r in recs:
        u, f = fplan.path_plans(r["collective"], r["algo"], 8, NELEMS)
        assert r["shmap_ppermute_ops"] == u.ppermute_ops, (r, u)
        assert r["pallas_fused_ppermute_ops"] == f.ppermute_ops, (r, f)
        print(f"{r['collective']},{r['algo']},{r['shmap_ppermute_ops']},"
              f"{r['pallas_fused_ppermute_ops']},{r['shmap_us']:.0f},"
              f"{r['pallas_fused_us']:.0f}")
        if recorder is not None:
            cfg = {"collective": r["collective"], "algo": r["algo"], "p": 8,
                   "nelems": NELEMS}
            recorder.add("fused_collectives_microbench", cfg,
                         "shmap_us", r["shmap_us"])
            recorder.add("fused_collectives_microbench", cfg,
                         "pallas_fused_us_interpret", r["pallas_fused_us"])
            recorder.add("fused_collectives_microbench", cfg,
                         "ppermute_ops", r["shmap_ppermute_ops"])


if __name__ == "__main__":
    run()
