"""Bucketed vs per-leaf gradient collectives on the real train step.

8-host-device subprocess (the ``bench_jax_collectives`` convention), one
train-step compile per row on the reduced qwen3-32b layout at p=8:

  * ``ppermute_ops``   — collective-permute count from the compiled HLO
    (the α·log₂(p)-per-collective latency proxy): drops from
    O(leaves·log p) to O(buckets·log p);
  * ``wire_bytes``     — per-chip collective bytes from the HLO roofline
    parser (bucketing must not move more bytes, only fewer messages);
  * ``wall_time_ms``   — CPU wall time per step (interpret-mode caveat of
    the README applies: a sanity signal, not the perf claim);
  * ``n_buckets``      — the static plan the step traced with.

Asserted here (and, harder, in tests/train/test_bucketed_step.py): the
per-leaf/bucketed ppermute ratio is ≥ 5× and wire bytes do not grow.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

SNIPPET = r"""
import json, time
import jax, numpy as np
from repro.configs import base
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.train.step import (TrainConfig, bucket_decisions, make_train_step,
                              make_init_fns)
from repro.kernels.collectives import plan as kplan
from jax import set_mesh
from repro.train.data import DataConfig, make_batch
from repro.launch import hlo, dryrun

mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
cfg = base.reduced(base.get_config("qwen3-32b"))
key = jax.random.key(0)
params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
dcfg = DataConfig(global_batch=8, seq_len=64, vocab_size=cfg.vocab_size)
N_DP, REPS = 8, 3
rows = []

for backend, bb, tag, wire in (
        ("bine", 0, "per_leaf", "float32"),
        ("bine", -1, "bucketed", "float32"),
        ("auto", -1, "bucketed_auto", "float32"),
        ("bine", -1, "bucketed_int8", "int8")):
    tcfg = TrainConfig(backend=backend, dp_axes=("pod", "data"),
                       bucket_bytes=bb, wire_dtype=wire)
    step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, params_shapes)
    init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)
    with set_mesh(mesh):
        params = init_p(key)
        state = init_s(params)
        b = make_batch(dcfg, 0)
        batch = {k: jax.device_put(v, shardings["batch"][k])
                 for k, v in b.items()}
        state_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            state)
        params_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            params)
        batch_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            batch)
        txt = step_fn.lower(params_sds, state_sds, batch_sds).compile().as_text()
        counts = hlo.op_counts_from_text(txt)
        roof = hlo.analyze_text(txt, N_DP, 4)
        # warmup + timed steps (donated args: re-put each call)
        host_p = jax.tree.map(np.asarray, params)
        host_s = jax.tree.map(np.asarray, state)
        def put():
            return (jax.device_put(host_p, jax.tree.map(
                        lambda x: x.sharding, params)),
                    jax.device_put(host_s, jax.tree.map(
                        lambda x: x.sharding, state)))
        p_, s_ = put()
        p_, s_, m = step_fn(p_, s_, batch)
        jax.block_until_ready(m["loss"])
        best = float("inf")
        for _ in range(REPS):
            p_, s_ = put()
            t0 = time.perf_counter()
            p_, s_, m = step_fn(p_, s_, batch)
            jax.block_until_ready(m["loss"])
            best = min(best, time.perf_counter() - t0)
    plan = shardings["bucket_plan"]
    # scheduled wire bytes per step (RS + AG over every bucket at ITS
    # resolved wire dtype, scale metadata included) — the analytic twin
    # of the tracer's per-link accounting
    wps = 0.0
    if plan is not None:
        for b, (_, rs_w, _, ag_w) in zip(plan.buckets,
                                         bucket_decisions(tcfg, plan)):
            n = b.row_elems * N_DP
            wps += kplan.wire_payload_bytes(
                "reduce_scatter", "bine", N_DP, n, rs_w)
            wps += kplan.wire_payload_bytes("allgather", "bine", N_DP, n, ag_w)
    rows.append({
        "tag": tag, "backend": backend, "bucket_bytes": bb,
        "wire_dtype": wire,
        "n_buckets": len(plan.buckets) if plan is not None else 0,
        "ppermute_ops": counts.get("collective-permute", 0)
                        + counts.get("collective-permute-start", 0),
        "wire_bytes_per_chip": roof.coll_bytes_per_chip,
        "wire_bytes_per_step": wps,
        "wall_time_ms": best * 1e3,
    })

per_leaf = next(r for r in rows if r["tag"] == "per_leaf")
for r in rows:
    if r["tag"] == "per_leaf":
        continue
    ratio = per_leaf["ppermute_ops"] / max(r["ppermute_ops"], 1)
    assert ratio >= 5.0, (per_leaf["ppermute_ops"], r["ppermute_ops"])
    assert r["wire_bytes_per_chip"] <= per_leaf["wire_bytes_per_chip"] * 1.01, \
        (r["tag"], r["wire_bytes_per_chip"], per_leaf["wire_bytes_per_chip"])
f32b = next(r for r in rows if r["tag"] == "bucketed")
i8b = next(r for r in rows if r["tag"] == "bucketed_int8")
# int8 wires (1 + 4/256 B/elem) must cut scheduled bytes ~4x vs f32
assert i8b["wire_bytes_per_step"] < 0.3 * f32b["wire_bytes_per_step"], \
    (i8b["wire_bytes_per_step"], f32b["wire_bytes_per_step"])
print("BENCH_JSON " + json.dumps(rows))
"""


def run(recorder=None) -> None:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(SNIPPET)],
                          capture_output=True, text=True, env=env,
                          timeout=3000)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bucketed-grads bench failed\n{proc.stdout[-2000:]}\n"
            f"{proc.stderr[-2000:]}")
    rows = None
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_JSON "):
            rows = json.loads(line[len("BENCH_JSON "):])
    assert rows, proc.stdout[-2000:]

    hdr = ("tag", "backend", "wire_dtype", "n_buckets", "ppermute_ops",
           "wire_bytes_per_chip", "wire_bytes_per_step", "wall_time_ms")
    print(",".join(hdr))
    for r in rows:
        print(",".join(f"{r[h]:.4g}" if isinstance(r[h], float) else str(r[h])
                       for h in hdr))
        if recorder is not None:
            cfg = {"tag": r["tag"], "backend": r["backend"],
                   "bucket_bytes": r["bucket_bytes"],
                   "wire_dtype": r["wire_dtype"]}
            for m in ("n_buckets", "ppermute_ops", "wire_bytes_per_chip",
                      "wire_bytes_per_step", "wall_time_ms"):
                recorder.add("bucketed_grads", cfg, m, r[m])
    per_leaf = next(r for r in rows if r["tag"] == "per_leaf")
    bucketed = next(r for r in rows if r["tag"] == "bucketed")
    print(f"# ppermute reduction: {per_leaf['ppermute_ops']:.0f} -> "
          f"{bucketed['ppermute_ops']:.0f} "
          f"({per_leaf['ppermute_ops'] / bucketed['ppermute_ops']:.1f}x)")


if __name__ == "__main__":
    run()
