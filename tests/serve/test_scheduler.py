"""Continuous-batching scheduler: the equivalence property on the
8-device mesh, slot recycling, EOS retirement, and host-side admission
logic against a fake engine (no devices).

The load-bearing property: per-request greedy decodes under mixed prompt
lengths + staggered arrivals are *identical* to running each request
alone in a 1-page pool — pages are computationally independent and RNG is
keyed per (request, token-index), so batch composition can never leak
into a request's output stream.
"""

import numpy as np
import pytest

from repro.serve.kvcache import SlotAllocator
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (ContinuousBatchingScheduler, Request,
                                   poisson_trace)

# ---------------------------------------------------------------------------
# Host-side logic against a fake engine (fast; exercises admission, slot
# recycling, retirement, and stats without any model)
# ---------------------------------------------------------------------------

_V = 32


class _FakeFns:
    """Deterministic stand-in engine: logits are a one-hot of pos % V, so
    a request admitted with prompt length L greedily generates
    L, L, L+1, L+2, ... (mod V) regardless of batch composition."""

    def __init__(self, n_slots):
        self.n_slots = n_slots
        self.shardings = {"plan": {}}
        self.trace_counts = {}
        self.insert = self._insert
        self.decode_slots = self._decode
        self.evict = self._evict

    def init_pool(self):
        return {"pos": np.zeros(self.n_slots, np.int64)}

    @staticmethod
    def _onehot(idx):
        out = np.zeros((len(idx), _V), np.float32)
        out[np.arange(len(idx)), np.asarray(idx) % _V] = 1.0
        return out

    def _insert(self, params, pool, tokens, length, slot):
        pool["pos"][slot] = int(length)
        return self._onehot([int(length)]), pool

    def _decode(self, params, pool, tokens, active):
        logits = self._onehot(pool["pos"])
        pool["pos"] += np.asarray(active, np.int64)
        return logits, pool

    def _evict(self, pool, slot):
        pool["pos"][slot] = 0
        return pool


def _fake_sched(n_slots, max_seq_len=64, top_p=0.0):
    import repro.configs.gemma3_4b  # noqa: F401  (registers the arch)
    from repro.configs import base
    cfg = base.reduced(base.get_config("gemma3-4b"))
    return ContinuousBatchingScheduler(
        cfg, _FakeFns(n_slots), params=None, n_slots=n_slots,
        max_seq_len=max_seq_len, top_p=top_p)


def _expected(L, n):
    """The fake engine's greedy stream for prompt length L."""
    return [L % _V] + [(L + i) % _V for i in range(n - 1)]


def test_fake_engine_streams_and_recycling():
    sched = _fake_sched(n_slots=2)
    reqs = [Request(rid=i, prompt=np.zeros(L, np.int32), max_new_tokens=5,
                    arrival=float(a))
            for i, (L, a) in enumerate([(3, 0.0), (7, 0.0), (11, 1.0),
                                        (20, 9.0)])]
    for r in reqs:
        sched.submit(r)
    stats = sched.run()
    for r in reqs:
        assert r.finished and r.finish_reason == "length"
        assert r.generated == _expected(len(r.prompt), 5), r.rid
    # 4 requests through 2 pages: every page recycled
    assert stats["inserts"] == 4
    assert stats["peak_occupancy"] == 2
    assert 0 < stats["mean_occupancy"] <= 2
    # arrival at t=9 with an idle pool: clock fast-forwards, not spins
    assert reqs[3].admitted_at == 9.0


def test_fake_engine_eos_retirement():
    sched = _fake_sched(n_slots=1)
    # stream for L=6 is [6, 6, 7, 8, ...]: eos_id=8 must stop after 4 tokens
    req = Request(rid=0, prompt=np.zeros(6, np.int32), max_new_tokens=50,
                  eos_id=8)
    sched.submit(req)
    sched.run()
    assert req.finish_reason == "eos"
    assert req.generated == [6, 6, 7, 8]
    # first-token EOS retires at admission, before any decode step
    sched2 = _fake_sched(n_slots=1)
    req2 = Request(rid=1, prompt=np.zeros(9, np.int32), max_new_tokens=50,
                   eos_id=9)
    sched2.submit(req2)
    sched2.run()
    assert req2.generated == [9] and req2.finish_reason == "eos"


def test_queue_span_per_admitted_request_on_the_host_clock(monkeypatch):
    """One ``serve.queue`` span per admitted request, on the scheduler's
    host clock (``perf_counter``): it starts at ``submit()`` and lasts
    until the request's insert starts."""
    from types import SimpleNamespace
    from repro.obs import metrics, timeline
    from repro.serve import scheduler as sched_mod

    ticks = iter(range(1000, 100000, 7))
    clock = SimpleNamespace(now=None)

    def perf_counter():
        clock.now = float(next(ticks))
        return clock.now

    monkeypatch.setattr(sched_mod, "time",
                        SimpleNamespace(perf_counter=perf_counter))
    tl = timeline.Timeline()
    monkeypatch.setattr(timeline, "_TIMELINE", tl)
    prev = metrics.set_enabled(True)
    try:
        sched = _fake_sched(n_slots=1)
        insert_start = {}
        fake_insert = sched.fns.insert

        def insert(params, pool, tokens, length, slot):
            insert_start[int(length)] = clock.now
            return fake_insert(params, pool, tokens, length, slot)

        sched.fns.insert = insert
        reqs = [Request(rid=i, prompt=np.zeros(L, np.int32),
                        max_new_tokens=3) for i, L in enumerate((3, 5, 8))]
        for r in reqs:
            sched.submit(r)
        submitted = {r.rid: r.queued_at for r in reqs}
        sched.run()
    finally:
        metrics.set_enabled(prev)
    spans = [e for e in tl.events if e.name == "serve.queue"]
    assert [e.args["rid"] for e in spans] == [0, 1, 2]
    for e, r in zip(spans, reqs):
        assert e.lane == "serve"
        assert e.ts_us == submitted[r.rid] * 1e6
        assert e.ts_us + e.dur_us == insert_start[len(r.prompt)] * 1e6
    # one slot: each request's insert starts after the one before it
    ends = [e.ts_us + e.dur_us for e in spans]
    assert ends == sorted(ends) and len(set(ends)) == 3


def test_submit_validation():
    sched = _fake_sched(n_slots=1, max_seq_len=16)
    with pytest.raises(ValueError, match="exceeds page size"):
        sched.submit(Request(rid=0, prompt=np.zeros(10, np.int32),
                             max_new_tokens=7))
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit(Request(rid=1, prompt=np.zeros(4, np.int32),
                             max_new_tokens=0))
    # top_k shapes the compiled sampler: mismatches must fail loudly, not
    # silently sample full-vocab
    with pytest.raises(ValueError, match="top_k"):
        sched.submit(Request(rid=2, prompt=np.zeros(4, np.int32),
                             max_new_tokens=2,
                             sampling=SamplingParams(top_k=8)))
    # top_p selects the compiled sampler's nucleus path: pool-global too
    with pytest.raises(ValueError, match="top_p"):
        sched.submit(Request(rid=3, prompt=np.zeros(4, np.int32),
                             max_new_tokens=2,
                             sampling=SamplingParams(top_p=0.9)))


def test_top_p_pool_admission_and_streams():
    """A top_p pool admits matching (or default) requests; greedy streams
    through the nucleus sampler are unchanged (argmax is always kept)."""
    sched = _fake_sched(n_slots=2, top_p=0.9)
    ok = Request(rid=0, prompt=np.zeros(5, np.int32), max_new_tokens=4,
                 sampling=SamplingParams(top_p=0.9))
    default = Request(rid=1, prompt=np.zeros(7, np.int32), max_new_tokens=4)
    sched.submit(ok)
    sched.submit(default)
    with pytest.raises(ValueError, match="top_p"):
        sched.submit(Request(rid=2, prompt=np.zeros(3, np.int32),
                             max_new_tokens=2,
                             sampling=SamplingParams(top_p=0.5)))
    sched.run()
    assert ok.generated == _expected(5, 4)
    assert default.generated == _expected(7, 4)


def test_slot_allocator_contract():
    al = SlotAllocator(3)
    a, b = al.acquire(), al.acquire()
    assert (a, b) == (0, 1) and al.n_occupied == 2
    al.release(a)
    with pytest.raises(ValueError, match="double-freed"):
        al.release(a)
    # FIFO: freed page 0 goes behind the never-used page 2
    assert al.acquire() == 2 and al.acquire() == 0 and al.acquire() is None


def test_poisson_trace_shape():
    trace = poisson_trace(10, rate=0.5, prompt_lens=(4, 12),
                          max_new_tokens=8, vocab_size=100, seed=3)
    arr = [r.arrival for r in trace]
    assert arr == sorted(arr) and all(a > 0 for a in arr)
    assert all(4 <= len(r.prompt) <= 12 for r in trace)
    assert len({r.rid for r in trace}) == 10


def test_poisson_trace_sessions_leave_tokens_unchanged():
    """Session ids are drawn after the prompts: the tagged trace carries
    byte-identical token content to the untagged one."""
    plain = poisson_trace(10, rate=0.5, prompt_lens=(4, 12),
                          max_new_tokens=8, vocab_size=100, seed=3)
    tagged = poisson_trace(10, rate=0.5, prompt_lens=(4, 12),
                           max_new_tokens=8, vocab_size=100, seed=3,
                           n_sessions=3)
    assert all(r.session is None for r in plain)
    assert all(r.session in {"s0", "s1", "s2"} for r in tagged)
    for a, b in zip(plain, tagged):
        assert (a.prompt == b.prompt).all() and a.arrival == b.arrival


def test_per_request_latency_stats():
    """Per-request admission wait / TTFT / e2e in virtual ticks, plus the
    nearest-rank percentile summary in stats()."""
    from repro.serve.scheduler import _pct, latency_summary

    sched = _fake_sched(n_slots=1)
    # n_slots=1 serializes: rid 1 waits for rid 0 to retire
    r0 = Request(rid=0, prompt=np.zeros(3, np.int32), max_new_tokens=4,
                 arrival=0.0)
    r1 = Request(rid=1, prompt=np.zeros(5, np.int32), max_new_tokens=4,
                 arrival=0.0)
    sched.submit(r0)
    sched.submit(r1)
    stats = sched.run()

    recs = {r["rid"]: r for r in sched.request_latencies()}
    assert set(recs) == {0, 1}
    assert recs[0]["admission_wait"] == 0.0
    assert recs[1]["admission_wait"] > 0.0     # blocked on the busy page
    for r in recs.values():
        # insert emits the first token at admission: TTFT == wait here
        assert r["ttft"] == r["admission_wait"]
        assert r["e2e"] >= r["ttft"] and r["tokens"] == 4

    lat = stats["latency"]
    assert lat["n"] == 2
    assert lat["admission_wait_p50"] == 0.0
    assert lat["admission_wait_p99"] == recs[1]["admission_wait"]
    assert lat["e2e_p50"] <= lat["e2e_p99"]

    # nearest-rank percentiles: deterministic, no interpolation
    assert _pct([], 50.0) == 0.0
    assert _pct([3.0, 1.0, 2.0], 50.0) == 2.0
    assert _pct([3.0, 1.0, 2.0], 99.0) == 3.0
    assert latency_summary([])["n"] == 0.0


# ---------------------------------------------------------------------------
# The real engine on the 8-device mesh: continuous-batching equivalence
# ---------------------------------------------------------------------------

EQUIV_CODE = r"""
import jax, numpy as np
from jax import set_mesh
from repro.configs import base
from repro.models import transformer as T
from repro.serve.engine import ServeConfig, make_serve_fns
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import ContinuousBatchingScheduler, Request

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = base.reduced(base.get_config("gemma3-4b"))   # dense + 5:1 local:global
S, MAX_NEW = 64, 6
params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(0))
scfg = ServeConfig(dp_axes=("data",))
fns3 = make_serve_fns(cfg, scfg, mesh, 3, S)
fns1 = make_serve_fns(cfg, scfg, mesh, 1, S)

rng = np.random.RandomState(5)
def mk(rid, L, arrival, eos=None):
    return Request(rid=rid, prompt=rng.randint(0, cfg.vocab_size, L).astype(np.int32),
                   max_new_tokens=MAX_NEW, arrival=arrival, eos_id=eos)

def run(fns, reqs, n_slots):
    sched = ContinuousBatchingScheduler(cfg, fns, params, n_slots, S, seed=11)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched

with set_mesh(mesh):
    # mixed prompt lengths (5..40, crossing the 16-token local window) +
    # staggered arrivals; 5 requests through 3 pages forces recycling
    reqs = [mk(0, 5, 0.0), mk(1, 23, 0.0), mk(2, 11, 1.5),
            mk(3, 40, 3.0), mk(4, 17, 6.0)]
    sched = run(fns3, reqs, 3)
    assert all(r.finished for r in reqs)
    assert sched.alloc.total_inserts == 5, "5 requests inserted"
    assert sched.alloc.peak_occupancy == 3, "pool saturated"
    mixed = {r.rid: list(r.generated) for r in reqs}

    # batch-1 references: identical token streams, exactly
    for r in reqs:
        solo = Request(rid=r.rid, prompt=r.prompt, max_new_tokens=MAX_NEW)
        run(fns1, [solo], 1)
        assert solo.generated == mixed[r.rid], (
            f"req {r.rid}: mixed {mixed[r.rid]} != solo {solo.generated}")
    print("EQUIV_OK", mixed)

    # temperature path: RNG is keyed per (request, token-index), so
    # sampled streams are batch-composition-independent too
    hot = SamplingParams(temperature=0.8)
    treqs = [Request(rid=20 + i, prompt=reqs[i].prompt,
                     max_new_tokens=MAX_NEW, arrival=float(i), sampling=hot)
             for i in range(3)]
    run(fns3, treqs, 3)
    for r in treqs:
        solo = Request(rid=r.rid, prompt=r.prompt, max_new_tokens=MAX_NEW,
                       sampling=hot)
        run(fns1, [solo], 1)
        assert solo.generated == r.generated, (
            f"temp req {r.rid}: mixed {r.generated} != solo {solo.generated}")
    print("TEMP_EQUIV_OK")

    # EOS retirement on the real engine: replay request 1 with eos_id set
    # to its own 3rd greedy token; generation must stop right there
    tgt = mixed[1][2]
    replay = Request(rid=99, prompt=reqs[1].prompt, max_new_tokens=MAX_NEW,
                     eos_id=int(tgt))
    run(fns1, [replay], 1)
    cut = mixed[1].index(tgt) + 1
    assert replay.generated == mixed[1][:cut], (replay.generated, mixed[1], tgt)
    assert replay.finish_reason == ("eos" if cut < MAX_NEW else "length")
    print("EOS_OK")

    # pool fns compiled once each despite 5 requests churning 3 pages
    for name in ("insert", "decode_slots", "evict"):
        assert fns3.trace_counts[name] == 1, (name, fns3.trace_counts)
    print("TRACE_OK", fns3.trace_counts)
print("ALL_OK")
"""


def test_continuous_batching_equivalence_8dev(subproc):
    out = subproc(EQUIV_CODE, devices=8, timeout=900)
    assert "EQUIV_OK" in out
    assert "TEMP_EQUIV_OK" in out
    assert "EOS_OK" in out
    assert "TRACE_OK" in out
    assert "ALL_OK" in out
