"""Multi-replica serve fleet: placement traffic + fleet-vs-single serving.

Two parts, matching the two claims the fleet subsystem makes:

1. **Placement (analytic, every packaged preset).**  For the 8-rank
   fleet shape (2 replicas x tp=4) the placement planner scores
   topology-aware (``chosen``) vs naive round-robin striping by predicted
   per-decode-step global-link bytes.  Asserted: the aware placement's
   global bytes are *strictly below* round-robin's on the grouped
   presets (lumi, leonardo, ...) — the paper's locality principle lifted
   to the fleet level.  On the torus both strategies are scored with the
   dimension-contiguous fallback and the argmin simply wins.

2. **Fleet vs single scaled-up replica (8-device subprocess).**  The
   same Poisson trace runs through (a) one replica with 3x the KV pages
   and (b) a 3-replica fleet of small replicas sharing one compiled
   engine, with a mid-trace drain + respawn.  Reported per serving
   shape: decode tok/s (wall clock) and p50/p99 end-to-end latency in
   virtual ticks.  Asserted: byte-identical per-request token streams —
   continuous-batching equivalence extended across routing and
   elasticity events.

Usage:
  PYTHONPATH=src:benchmarks python benchmarks/bench_serve_fleet.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

try:  # package import (benchmarks.run) or cwd convention (standalone)
    from benchmarks.common import emit
except ImportError:
    from common import emit

from repro.fleet.placement import decode_payloads, plan_placement
from repro.topology.presets import PRESETS, tier_split_or_none

#: grouped presets where aware placement must strictly beat round-robin
#: at the 8-rank acceptance shape (the torus ties: both fallback stripes
#: are dimension-aligned there)
STRICT_WIN = ("lumi", "leonardo")

#: the modeled fleet shape: an 8-rank allocation, 2 replicas at tp=4
SHAPE = dict(n_ranks=8, n_replicas=2, tp=4)

SNIPPET = r"""
import json, time
import jax, numpy as np
from jax import set_mesh
from repro.configs import base as cfgbase
from repro.fleet import Fleet, FleetConfig, FleetEvent
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.serve.engine import ServeConfig, make_serve_fns, page_len
from repro.serve.scheduler import poisson_trace

N_REQ, RATE, MAX_NEW, PMIN, PMAX, SEED = 14, 1.0, 10, 4, 16, 0
mesh = make_mesh((4, 2), ("data", "model"))
cfg = cfgbase.reduced(cfgbase.get_config("gemma3-4b"))
S = page_len(cfg, PMAX, MAX_NEW)
scfg = ServeConfig(dp_axes=("data",), backend="auto")
params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(SEED))

def serve(tag, n_replicas, n_slots, events):
    fns = make_serve_fns(cfg, scfg, mesh, n_slots, S)
    trace = poisson_trace(N_REQ, RATE, (PMIN, PMAX), MAX_NEW,
                          cfg.vocab_size, seed=SEED, n_sessions=4)
    fcfg = FleetConfig(n_replicas=n_replicas, n_slots=n_slots, seed=SEED)
    with set_mesh(mesh):
        fleet = Fleet(cfg, fns, params, fcfg, S)
        fleet.submit_trace(trace)
        # warmup tick: compiles insert + pooled decode for this pool shape
        fleet.step(events)
        warm_tokens = fleet.stats()["tokens_out"]
        t0 = time.time()
        while fleet.step(events):
            pass
        dt = time.time() - t0
    stats = fleet.stats()
    for name in ("insert", "decode_slots", "evict", "init_pool"):
        assert fns.trace_counts[name] <= 1, (name, fns.trace_counts)
    return {
        "shape": tag, "replicas": n_replicas, "slots": n_slots,
        "tok_s": (stats["tokens_out"] - warm_tokens) / max(dt, 1e-9),
        "tokens": stats["tokens_out"],
        "ticks": stats["ticks"],
        "decode_steps": stats["decode_steps"],
        "e2e_p50_ticks": stats["latency"]["e2e_p50"],
        "e2e_p99_ticks": stats["latency"]["e2e_p99"],
        "ttft_p99_ticks": stats["latency"]["ttft_p99"],
        "n_spilled": stats["routing"]["n_spilled"],
        "respawns": sum(r["respawns"] for r in stats["replicas"].values()),
    }, [list(map(int, r.generated)) for r in trace]

single, out_single = serve("single_3x", 1, 12, [])
fleet, out_fleet = serve("fleet_3x", 3, 4,
                         [FleetEvent(5, "drain", 1),
                          FleetEvent(10, "respawn", 1)])
assert out_single == out_fleet, "fleet changed a token stream"
print("BENCH_JSON " + json.dumps([single, fleet]))
"""


def run_placement(recorder=None):
    """Part 1: score aware vs round-robin on every packaged preset."""
    from repro.configs import base as cfgbase

    cfg = cfgbase.reduced(cfgbase.get_config("gemma3-4b"))
    payloads = decode_payloads(4, cfg.n_heads, cfg.head_dim, cfg.vocab_size)
    rows = []
    for preset in PRESETS:
        plan = plan_placement(preset, payloads=payloads, **SHAPE)
        aware, rr = plan.scores[plan.chosen], plan.scores["round_robin"]
        rows.append((preset,
                     "grouped" if tier_split_or_none(preset, 2) else "torus",
                     plan.chosen, aware.global_bytes, rr.global_bytes,
                     aware.tick_time_s * 1e6, rr.tick_time_s * 1e6))
        if recorder is not None:
            c = {"preset": preset, **SHAPE}
            recorder.add("serve_fleet", c, "aware_global_bytes_per_tick",
                         aware.global_bytes)
            recorder.add("serve_fleet", c, "rr_global_bytes_per_tick",
                         rr.global_bytes)
            recorder.add("serve_fleet", c, "aware_tick_us",
                         aware.tick_time_s * 1e6)
    emit(rows, header=("preset", "kind", "chosen", "aware_global_B",
                       "rr_global_B", "aware_tick_us", "rr_tick_us"))
    for preset in STRICT_WIN:
        plan = plan_placement(preset, payloads=payloads, **SHAPE)
        aware, rr = plan.scores[plan.chosen], plan.scores["round_robin"]
        assert aware.global_bytes < rr.global_bytes, (
            f"{preset}: aware placement must strictly beat round-robin "
            f"({aware.global_bytes} vs {rr.global_bytes})")
    print(f"# placement check passed: aware < round_robin global bytes "
          f"on {STRICT_WIN}")


def run_fleet_serve(recorder=None):
    """Part 2: 8-device fleet vs single scaled-up replica."""
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
            f"serve-fleet bench failed\n{proc.stdout[-2000:]}\n"
            f"{proc.stderr[-2000:]}")
    rows = None
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_JSON "):
            rows = json.loads(line[len("BENCH_JSON "):])
    assert rows, proc.stdout[-2000:]

    hdr = ("shape", "replicas", "slots", "tok_s", "ticks", "decode_steps",
           "e2e_p50_ticks", "e2e_p99_ticks", "ttft_p99_ticks", "n_spilled",
           "respawns")
    print(",".join(hdr))
    for r in rows:
        print(",".join(f"{r[h]:.4g}" if isinstance(r[h], float) else str(r[h])
                       for h in hdr))
        if recorder is not None:
            c = {"shape": r["shape"], "replicas": r["replicas"],
                 "slots": r["slots"]}
            for m in ("tok_s", "ticks", "decode_steps", "e2e_p50_ticks",
                      "e2e_p99_ticks", "ttft_p99_ticks", "n_spilled",
                      "respawns"):
                recorder.add("serve_fleet", c, m, r[m])
    print("# stream-equivalence check passed: fleet (with drain+respawn) "
          "== single scaled-up replica")


def run(recorder=None) -> None:
    run_placement(recorder)
    run_fleet_serve(recorder)


if __name__ == "__main__":
    run()
