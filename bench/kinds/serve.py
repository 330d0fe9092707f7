"""Serving cells: an open loop through the program's continuous-batching
scheduler (``serve.scheduler.ContinuousBatchingScheduler``) over its
paged pool (``serve.engine.make_serve_fns``: ``insert``, ``decode_slots``).

Requests are submitted when due, on the host clock; the scheduler admits
them against that clock instead of its virtual one.  Every served token is
stamped on the host clock when the host holds it.  After the window the
loop runs on, taking no new request, until every request due in the
window has its first token.  ``correct`` then compares a sample of the
finished requests (drawn from the seed, the longest among them) with the
plain reference's full forward pass over prompt and served tokens.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import core
from bench.model import model_config, weights_fn

#: share of the window after which a traced run starts recording (the
#: admission wait reads the requests before it), and seconds it records
TRACE_AT, TRACE_S = 0.75, 4.0
#: requests compared with the reference besides the longest
SAMPLE = 3


def stamped_scheduler(run, sched_cls, *a, **kw):
    """The program's scheduler, with every served token stamped."""

    class Stamped(sched_cls):
        last_insert = None

        def _record(self, slot, req, tok):
            if not req.generated:
                req.admit_t = self.last_insert
            req.stamps.append(time.perf_counter())
            super()._record(slot, req, tok)

    return Stamped(*a, **kw)


def build(run: core.Run):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeConfig, make_serve_fns, page_len

    sw = run.workload["serve"]
    devices = jax.devices()[:1]
    mesh = make_mesh((1, 1), ("data", "model"), devices)
    mcfg = model_config(run.config)
    S = page_len(mcfg, run.traffic["prompt_len"]["max"],
                 run.traffic["output_len"]["max"])
    fns = make_serve_fns(mcfg, ServeConfig(), mesh, sw["slots"], S)
    return mesh, devices, mcfg, fns, S


def instrument(run, fns, sched):
    """Host spans and call records around the pool's entry points."""
    from jax.profiler import TraceAnnotation

    def insert(*a):
        sched.last_insert = time.perf_counter()
        with TraceAnnotation("bench.insert"):
            return fns.insert(*a)

    def decode(params, pool, toks, active):
        act = np.flatnonzero(active)
        ctx = int(sum(len(sched._running[s].prompt)
                      + len(sched._running[s].generated) for s in act))
        t = time.perf_counter()
        with TraceAnnotation("bench.decode"):
            out = fns.decode_slots(params, pool, toks, active)
        run.decode_calls.append((t, time.perf_counter(), len(act), ctx))
        return out

    sampler = sched.sampler

    def sample(*a):
        with TraceAnnotation("bench.sample"):
            return sampler(*a)

    sched.fns = dataclasses.replace(fns, insert=insert, decode_slots=decode)
    sched.sampler = sample


def warm_up(sched, Request, prompt):
    """One request of two tokens: one insert, one decode step and the
    sampler's two shapes, the cell's own programs."""
    warm = Request(rid=-1, prompt=prompt, max_new_tokens=2)
    warm.stamps = []
    sched.submit(warm)
    while sched.step():
        pass


def make_requests(run):
    gen = core.load_module("traffic", run.traffic["generator"])
    return gen.make(run.traffic, core.seed32(run.seed, 1), run.seconds,
                    run.config["vocab_size"])


def serve_window(run, sched, reqs, Request):
    """Submit ``reqs`` as they fall due; step until every one has its
    first token.  Returns the host clock of the window's start and the
    requests."""
    from jax.profiler import TraceAnnotation
    pending, live = list(reqs), []
    tracer = core.WindowTrace(TRACE_AT * run.seconds, TRACE_S) \
        if run.trace else None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if tracer:
            tracer.tick(now)
        while pending and pending[0]["due"] <= now:
            r = pending.pop(0)
            req = Request(rid=len(live), prompt=r["prompt"],
                          max_new_tokens=r["max_new"], arrival=r["due"])
            req.stamps = []
            live.append(req)
            sched.submit(req)
        if not pending and all(q.stamps for q in live) \
                and not (tracer and tracer.pending):
            break
        if not sched.n_running and not sched.n_waiting:
            wait = pending[0]["due"] - now if pending else 0.002
            time.sleep(min(max(wait, 0.0), 0.002))
            continue
        sched.clock = now
        with TraceAnnotation("bench.sched_step"):
            sched.step()
    if tracer:
        run.trace_summary = tracer.finish()
        run.extra["trace_host_window"] = tracer.host
    return t0, live


def run_cell(run: core.Run) -> None:
    import jax
    from repro.serve.scheduler import ContinuousBatchingScheduler, Request

    sw = run.workload["serve"]
    mesh, devices, mcfg, fns, S = build(run)
    reqs = make_requests(run)
    s32 = core.seed32(run.seed)
    with jax.set_mesh(mesh):
        params = weights_fn(run.config)(s32)
        sched = stamped_scheduler(run, ContinuousBatchingScheduler, mcfg,
                                  fns, params, sw["slots"], S)
        instrument(run, fns, sched)
        warm_up(sched, Request, reqs[0]["prompt"])
        run.decode_calls.clear()
        run.setup_s = time.perf_counter() - run.t_start
        t0, live = serve_window(run, sched, reqs, Request)
        run.window = (t0, t0 + run.seconds)
        run.device["memory_peak_bytes"] = core.memory_peak(devices)
        run.extra["trace_counts"] = dict(fns.trace_counts)
    for q in live:
        run.requests.append({
            "due": t0 + q.arrival, "admitted": getattr(q, "admit_t", None),
            "stamps": q.stamps, "prompt_len": len(q.prompt),
            "finished": q.finished})
    run.attempted = len(live)
    run.failed = sum(1 for q in live if not q.stamps)
    done = [q for q in live if q.finished]
    pick = sample_finished(run.seed, done)
    seqs = [(q.prompt, list(q.generated)) for q in pick]
    del sched, params, fns
    gc.collect()
    gaps = reference_gaps(run, seqs)
    worst = max(float(g.max()) for g in gaps)
    run.extra["compared_tokens"] = int(sum(len(g) for g in gaps))
    run.checks = [("logit_gap", worst, run.workload["limits"]["logit_gap"])]


def sample_finished(seed, done):
    """The longest finished request and ``SAMPLE`` more drawn from the
    seed."""
    if not done:
        return []
    longest = max(done, key=lambda q: len(q.generated))
    rest = [q for q in done if q is not longest]
    rng = np.random.default_rng(core.seed32(seed, 2))
    k = min(SAMPLE, len(rest))
    idx = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in idx]


def reference_gaps(run, seqs, mode="f32"):
    from bench import reference as R
    tree = weights_fn(run.config)(core.seed32(run.seed))
    length = run.traffic["prompt_len"]["max"] + run.traffic["output_len"]["max"]
    return R.serve_gaps(run.config, tree, seqs, length, mode=mode)
