"""Chaos equivalence on the real engine (8-device subprocess): a crash
mid-tick AND a straggler tick must be invisible in every request's token
stream — the supervised chaotic fleet produces byte-identical outputs to
the undisturbed fleet, for greedy AND temperature sampling.

This is the acceptance property of the resilience subsystem: crash ->
eject (generated prefix folded into the prompt) -> replay -> respawn is
a pure reshuffling of WHERE tokens are computed, never WHAT tokens come
out, because pages are computationally independent and RNG is keyed per
(request, token-index).
"""

CHAOS_EQUIV_CODE = r"""
import jax, numpy as np
from jax import set_mesh
from repro.configs import base
from repro.fleet import Fleet, FleetConfig
from repro.models import transformer as T
from repro.resilience import (ChaosSchedule, FaultEvent, FleetSupervisor,
                              SupervisorConfig)
from repro.serve.engine import ServeConfig, make_serve_fns
from repro.serve.scheduler import poisson_trace

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = base.reduced(base.get_config("gemma3-4b"))
S, MAX_NEW, SEED = 64, 6, 11
params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(0))
scfg = ServeConfig(dp_axes=("data",))
fns = make_serve_fns(cfg, scfg, mesh, 3, S)     # 3 pages per replica

def run(chaos, temperature):
    trace = poisson_trace(10, 1.0, (5, 40), MAX_NEW, cfg.vocab_size,
                          seed=5, temperature=temperature, n_sessions=3)
    fcfg = FleetConfig(n_replicas=3, n_slots=3, seed=SEED)
    fleet = Fleet(cfg, fns, params, fcfg, S)
    fleet.submit_trace(trace)
    if chaos is None:
        fleet.run()
        sup = None
    else:
        sup = FleetSupervisor(fleet, chaos,
                              SupervisorConfig(respawn_delay=2))
        sup.run()
    assert all(r.finished for r in trace)
    return {r.rid: list(map(int, r.generated)) for r in trace}, sup

chaos = ChaosSchedule([FaultEvent(2, "crash", 0),
                       FaultEvent(4, "straggler", 1, 8.0)])
with set_mesh(mesh):
    for temperature, tag in ((0.0, "GREEDY"), (0.8, "TEMP")):
        calm, _ = run(None, temperature)
        chaotic, sup = run(chaos, temperature)
        assert calm == chaotic, (tag, calm, chaotic)
        rec = sup.crash_log[0]
        assert len(sup.crash_log) == 1 and rec.replica == 0
        assert rec.displaced >= 1, "crash must eject real in-flight work"
        assert rec.ttr == 2 and sup.mttr() == 2.0
        res = sup.report()["resilience"]
        assert res["final_health"][0]["respawns"] == 1
        assert res["chaos_signature"] == chaos.signature()
        print(tag + "_CHAOS_EQUIV_OK mttr=%s" % sup.mttr())
print("ALL_OK")
"""


def test_chaos_equivalence_8dev(subproc):
    out = subproc(CHAOS_EQUIV_CODE, devices=8, timeout=900)
    assert "GREEDY_CHAOS_EQUIV_OK" in out
    assert "TEMP_CHAOS_EQUIV_OK" in out
    assert "ALL_OK" in out
