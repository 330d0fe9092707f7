"""Fleet-vs-single equivalence on the real engine (8-device subprocess):
the same trace + seed must produce byte-identical per-request token
streams from one replica and from an N-replica fleet with drains and
respawns mid-trace — for greedy AND temperature sampling.

This is the acceptance property of the fleet subsystem: routing, drains,
and respawns are invisible in every request's output because pages are
computationally independent and RNG is keyed per (request, token-index),
with every replica seeded identically.
"""

FLEET_EQUIV_CODE = r"""
import jax, numpy as np
from jax import set_mesh
from repro.configs import base
from repro.fleet import Fleet, FleetConfig, FleetEvent
from repro.models import transformer as T
from repro.serve.engine import ServeConfig, make_serve_fns
from repro.serve.scheduler import poisson_trace

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = base.reduced(base.get_config("gemma3-4b"))
S, MAX_NEW, SEED = 64, 6, 11
params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(0))
scfg = ServeConfig(dp_axes=("data",))
fns3 = make_serve_fns(cfg, scfg, mesh, 3, S)   # 3 pages per fleet replica
fns9 = make_serve_fns(cfg, scfg, mesh, 9, S)   # the scaled-up single

def run(fns, n_replicas, n_slots, events, temperature):
    trace = poisson_trace(10, 1.0, (5, 40), MAX_NEW, cfg.vocab_size,
                          seed=5, temperature=temperature, n_sessions=3)
    fcfg = FleetConfig(n_replicas=n_replicas, n_slots=n_slots, seed=SEED)
    fleet = Fleet(cfg, fns, params, fcfg, S)
    fleet.submit_trace(trace)
    stats = fleet.run(events=events)
    assert all(r.finished for r in trace)
    return {r.rid: list(map(int, r.generated)) for r in trace}, stats

events = [FleetEvent(4, "drain", 1), FleetEvent(9, "respawn", 1),
          FleetEvent(7, "drain", 2)]
with set_mesh(mesh):
    for temperature, tag in ((0.0, "GREEDY"), (0.8, "TEMP")):
        single, _ = run(fns9, 1, 9, [], temperature)
        fleet, stats = run(fns3, 3, 3, events, temperature)
        assert single == fleet, (tag, single, fleet)
        assert stats["replicas"][1]["respawns"] == 1
        assert stats["replicas"][2]["state"] == "stopped"
        print(tag + "_EQUIV_OK")
    # N replicas over one compiled engine: pool fns traced once total
    for name in ("insert", "decode_slots", "evict", "init_pool"):
        assert fns3.trace_counts[name] == 1, (name, fns3.trace_counts)
    print("SHARED_TRACE_OK", fns3.trace_counts)
print("ALL_OK")
"""


def test_fleet_vs_single_equivalence_8dev(subproc):
    out = subproc(FLEET_EQUIV_CODE, devices=8, timeout=900)
    assert "GREEDY_EQUIV_OK" in out
    assert "TEMP_EQUIV_OK" in out
    assert "SHARED_TRACE_OK" in out
    assert "ALL_OK" in out
