"""Executable-collective microbenchmark: wall time of the shard_map
implementations on 8 host devices (sanity: the schedules execute; CPU
timings are NOT the performance claim — the roofline is).

Run in a subprocess so the 8-device flag never leaks into other benches.
"""

import os
import subprocess
import sys
import textwrap

SNIPPET = """
import time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.collectives import api
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))

rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(8, 1 << 16).astype(np.float32))
print("backend,collective,us_per_call")
for backend in ("bine", "recdoub", "ring", "xla"):
    cfg = api.CollectiveConfig(backend=backend, small_cutoff_bytes=0)
    for coll, fn in (
        ("allreduce", lambda v: api.allreduce(v, "x", cfg)),
        ("reduce_scatter", lambda v: api.reduce_scatter(v.reshape(-1), "x", cfg)),
        ("allgather", lambda v: api.allgather(v.reshape(-1), "x", cfg)),
    ):
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                                  out_specs=P("x")))
        f(x)  # compile
        t0 = time.perf_counter()
        for _ in range(20):
            out = f(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 20 * 1e6
        print(f"{backend},{coll},{dt:.1f}")
"""


def run():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(SNIPPET)],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    print(proc.stdout.strip())


if __name__ == "__main__":
    run()
