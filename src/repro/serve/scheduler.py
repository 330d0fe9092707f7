"""Continuous-batching request scheduler over the paged-KV serve engine.

Lifecycle (see README "Serving" for the full diagram)::

    submit() --> WAITING --admission (free page + arrived)--> RUNNING
    RUNNING  --decode step + sample--> RUNNING | FINISHED (EOS / budget)
    FINISHED --release page--> page recycled to the next WAITING request

Each scheduler iteration (:meth:`ContinuousBatchingScheduler.step`):

  1. **Admit**: while a page is free and the head of the arrival queue has
     arrived, ``insert`` the request (padded prefill, one compile covers
     every prompt length) and sample its first token from the prompt's
     last-position logits.
  2. **Decode**: one ``decode_slots`` step over the whole pool — every
     RUNNING request advances one token regardless of when it was admitted
     or how long its prompt was; retired pages hold their position.
  3. **Sample + retire**: per-slot greedy/temperature/top-k/top-p sampling
     (RNG keyed per (request, token-index), so draws are independent of
     batch composition), then EOS / max-token retirement frees pages for
     the next admission.

The decode loop therefore stays saturated under heterogeneous traffic —
exactly the regime where the topology-aware collective plan
(``shardings["plan"]``, consumed by the sampler's logits re-assembly)
matters.  Time is virtual: one scheduler iteration = one time unit, and
request arrivals (e.g. from :func:`poisson_trace`) are compared against
that clock, which keeps every run exactly reproducible.

What it records in real time (``repro.obs.timeline``): each admitted
request's ``serve.queue`` span, from ``submit()`` to the start of its
insert, on ``perf_counter``; and, for a profiler only, ``serve.admit``,
``serve.decode``, ``serve.sample`` and ``serve.retire`` around each
iteration's phases.

The equivalence property tests/serve/test_scheduler.py locks in: because
pages are computationally independent and RNG is per-request, a request's
output stream is identical whether it runs alone in a 1-page pool or
interleaved with arbitrary other traffic.
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import timeline as obs_timeline
from repro.obs.timeline import host_span
from repro.serve import sampling as S
from repro.serve.kvcache import SlotAllocator


@dataclass
class Request:
    """One generation request.  ``prompt`` is a 1-D int32 token array."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival: float = 0.0
    sampling: S.SamplingParams = field(default_factory=S.SamplingParams)
    eos_id: Optional[int] = None
    # -- filled by the scheduler --
    generated: List[int] = field(default_factory=list)
    finished: bool = False
    finish_reason: Optional[str] = None   # "eos" | "length"
    admitted_at: Optional[float] = None
    #: host clock (``time.perf_counter()`` seconds) of the last submit()
    queued_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    #: routing affinity: requests sharing a session hash to the same
    #: replica in a fleet (KV/prefix reuse); None falls back to a hash of
    #: the prompt's leading tokens (repro.fleet.router.affinity_key)
    session: Optional[str] = None


class ContinuousBatchingScheduler:
    """Drives a :class:`repro.serve.engine.ServeFns` pool to completion."""

    def __init__(self, model_cfg, fns, params, n_slots: int,
                 max_seq_len: int, top_k: int = 0, top_p: float = 0.0,
                 seed: int = 0):
        if fns.insert is None:
            raise NotImplementedError(
                f"continuous batching unsupported for {model_cfg.name!r}: "
                "recurrent blocks, MoE capacity dispatch, and modality "
                "frontends cannot take the padded-insert path (see "
                "engine.pool_supported)")
        self.cfg = model_cfg
        self.fns = fns
        self.params = params
        self.n_slots = n_slots
        self.max_seq_len = max_seq_len
        self.top_k = top_k
        self.top_p = top_p
        self.alloc = SlotAllocator(n_slots)
        self.pool = fns.init_pool()
        self.sampler = S.make_sampler(top_k, top_p,
                                      plan=fns.shardings.get("plan"))
        self.key = jax.random.key(seed)
        self.clock = 0.0
        self.tokens_out = 0
        self._waiting: list = []            # heap of (arrival, rid, Request)
        self._running: Dict[int, Request] = {}   # slot -> Request
        #: per-retired-request latency record (virtual ticks), the input
        #: to stats()["latency"] and the fleet router's feedback loop
        self._latency_log: List[Dict[str, float]] = []
        # pooled per-slot sampling inputs (host mirrors)
        self._next_tok = np.zeros((n_slots, 1), np.int32)
        self._temps = np.zeros((n_slots,), np.float32)
        self._rids = np.zeros((n_slots,), np.int32)
        self._steps = np.zeros((n_slots,), np.int32)
        self._active = np.zeros((n_slots,), np.int32)

    # -- submission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        # final page occupancy = prompt + tokens still to generate; a
        # crash-replayed request arrives with its generated prefix folded
        # into the prompt (eject_all), so the budget counts the REMAINING
        # tokens — for a fresh request (generated empty) this is the
        # original prompt + budget check unchanged
        if (len(req.prompt) + req.max_new_tokens - len(req.generated)
                > self.max_seq_len):
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + budget "
                f"({req.max_new_tokens}) exceeds page size {self.max_seq_len}")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be >= 1")
        if req.sampling.top_k not in (0, self.top_k):
            raise ValueError(
                f"request {req.rid}: top_k={req.sampling.top_k} differs from "
                f"the pool sampler's top_k={self.top_k} (top_k shapes the "
                f"compiled sampler, so it is pool-global)")
        if req.sampling.top_p not in (0.0, self.top_p):
            raise ValueError(
                f"request {req.rid}: top_p={req.sampling.top_p} differs from "
                f"the pool sampler's top_p={self.top_p} (top_p selects the "
                f"compiled sampler's nucleus path, so it is pool-global)")
        req.queued_at = time.perf_counter()
        heapq.heappush(self._waiting, (req.arrival, req.rid, req))

    # -- internals ----------------------------------------------------------

    def _sample_one(self, logits, req: Request) -> int:
        with host_span("serve.sample"):
            tok = self.sampler(
                logits,
                np.asarray([req.sampling.temperature], np.float32),
                np.asarray([req.rid], np.int32),
                np.asarray([len(req.generated)], np.int32),
                self.key)
            return int(np.asarray(tok)[0])

    def _retire(self, slot: int, req: Request, reason: str) -> None:
        req.finished = True
        req.finish_reason = reason
        req.finished_at = self.clock
        self._latency_log.append({
            "rid": req.rid,
            "admission_wait": req.admitted_at - req.arrival,
            "ttft": req.first_token_at - req.arrival,
            "e2e": self.clock - req.arrival,
            "tokens": float(len(req.generated)),
        })
        if obs_metrics.enabled():
            reg = obs_metrics.get_registry()
            reg.inc("serve_requests_retired", 1.0, reason=reason)
            reg.observe("serve_request_ttft_ticks",
                        req.first_token_at - req.arrival)
            reg.observe("serve_request_e2e_ticks", self.clock - req.arrival)
        self.pool = self.fns.evict(self.pool, np.int32(slot))
        self.alloc.release(slot)
        self._active[slot] = 0
        del self._running[slot]

    def _record(self, slot: int, req: Request, tok: int) -> None:
        """Account one sampled token; retire or queue it as the next input."""
        req.generated.append(tok)
        if req.first_token_at is None:
            req.first_token_at = self.clock
        self.tokens_out += 1
        if req.eos_id is not None and tok == req.eos_id:
            self._retire(slot, req, "eos")
        elif len(req.generated) >= req.max_new_tokens:
            self._retire(slot, req, "length")
        else:
            self._next_tok[slot, 0] = tok

    def _admit(self) -> int:
        admitted = 0
        while (self._waiting and self._waiting[0][0] <= self.clock
               and self.alloc.free):
            _, _, req = heapq.heappop(self._waiting)
            slot = self.alloc.acquire()
            padded = np.zeros((1, self.max_seq_len), np.int32)
            padded[0, :len(req.prompt)] = req.prompt
            t = time.perf_counter()
            obs_timeline.get_timeline().span(
                "serve.queue", "serve", req.queued_at * 1e6,
                (t - req.queued_at) * 1e6, rid=req.rid)
            with host_span("serve.admit"):
                logits, self.pool = self.fns.insert(
                    self.params, self.pool, padded,
                    np.int32(len(req.prompt)), np.int32(slot))
                req.admitted_at = self.clock
                self._running[slot] = req
                self._temps[slot] = req.sampling.temperature
                self._rids[slot] = req.rid
                self._active[slot] = 1
                self._record(slot, req, self._sample_one(logits, req))
            admitted += 1
        return admitted

    # -- the loop -----------------------------------------------------------

    def step(self) -> bool:
        """One scheduler iteration.  Returns False when fully drained."""
        if not self._running and self._waiting:
            # idle pool: fast-forward the clock to the next arrival
            self.clock = max(self.clock, self._waiting[0][0])
        self._admit()
        if not self._running:
            return bool(self._waiting)
        for slot, req in self._running.items():
            self._steps[slot] = len(req.generated)
        with host_span("serve.decode"):
            logits, self.pool = self.fns.decode_slots(
                self.params, self.pool, self._next_tok, self._active)
        with host_span("serve.sample"):
            toks = np.asarray(self.sampler(
                logits, self._temps, self._rids, self._steps, self.key))
        self.alloc.tick()
        with host_span("serve.retire"):
            for slot, req in list(self._running.items()):
                self._record(slot, req, int(toks[slot]))
        self.clock += 1.0
        return bool(self._running or self._waiting)

    def run(self) -> dict:
        """Drain every submitted request; returns summary stats."""
        while self.step():
            pass
        return self.stats()

    # -- fleet hooks --------------------------------------------------------

    @property
    def n_running(self) -> int:
        return len(self._running)

    @property
    def n_waiting(self) -> int:
        return len(self._waiting)

    def eject_waiting(self) -> List[Request]:
        """Remove and return every not-yet-admitted request (arrival
        order).  In-flight requests are untouched — this is the admit-side
        half of a fleet drain: the ejected requests re-route to another
        replica while this one finishes what it already holds."""
        out = [req for _, _, req in sorted(self._waiting)]
        self._waiting.clear()
        return out

    def eject_all(self) -> List[Request]:
        """Crash-path eject: the waiting queue AND every in-flight
        request, the latter prepared for byte-identical replay by folding
        the generated prefix into the prompt.

        Sampling is keyed per (rid, token-index) and the pool is
        re-prefilled from the extended prompt on re-admission, so the
        request's next sampled token — index ``len(generated)`` — is the
        token the fault-free run would have produced; ``generated`` is
        left intact so retirement (``max_new_tokens``) and ttft stats
        survive the crash.  The pool state itself is abandoned (the
        crashed replica's scheduler is discarded on respawn).
        """
        out = self.eject_waiting()
        for slot in sorted(self._running):
            req = self._running[slot]
            if req.generated:
                req.prompt = np.concatenate(
                    [req.prompt,
                     np.asarray(req.generated, np.int32)]).astype(np.int32)
            self.alloc.release(slot)
            self._active[slot] = 0
            out.append(req)
        self._running.clear()
        return sorted(out, key=lambda r: (r.arrival, r.rid))

    def request_latencies(self) -> List[Dict[str, float]]:
        """Per-retired-request latency records (virtual ticks):
        ``{rid, admission_wait, ttft, e2e, tokens}``."""
        return list(self._latency_log)

    def stats(self) -> dict:
        return {
            "decode_steps": self.alloc.decode_steps,
            "tokens_out": self.tokens_out,
            "inserts": self.alloc.total_inserts,
            "mean_occupancy": self.alloc.mean_occupancy,
            "peak_occupancy": self.alloc.peak_occupancy,
            "clock": self.clock,
            "latency": latency_summary(self._latency_log),
        }


def _pct(xs: List[float], q: float) -> float:
    """Nearest-rank percentile (deterministic, no interpolation)."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    k = max(0, min(len(xs) - 1, int(np.ceil(q / 100.0 * len(xs))) - 1))
    return float(xs[k])


def latency_summary(log: List[Dict[str, float]]) -> Dict[str, float]:
    """p50/p99 (virtual ticks) over per-request latency records:
    admission wait (arrival -> admitted), time-to-first-token (the first
    token samples during the admission tick, so ttft == admission wait
    today — tracked separately so chunked prefill can change that), and
    end-to-end (arrival -> retirement)."""
    out: Dict[str, float] = {"n": float(len(log))}
    for metric in ("admission_wait", "ttft", "e2e"):
        vals = [r[metric] for r in log]
        out[f"{metric}_p50"] = _pct(vals, 50.0)
        out[f"{metric}_p99"] = _pct(vals, 99.0)
    return out


def poisson_trace(n_requests: int, rate: float, prompt_lens,
                  max_new_tokens: int, vocab_size: int, seed: int = 0,
                  temperature: float = 0.0,
                  eos_id: Optional[int] = None,
                  n_sessions: Optional[int] = None) -> List[Request]:
    """Poisson arrival trace: exponential inter-arrival gaps at ``rate``
    requests per scheduler step, prompt lengths uniform over
    ``prompt_lens`` (an inclusive ``(lo, hi)`` pair or explicit list).

    ``n_sessions`` tags requests with session ids ``"s0".."s{n-1}"``
    (uniform; drawn after the prompts, so traces with and without
    sessions carry identical token content) — the affinity signal the
    fleet router co-locates for KV/prefix reuse."""
    rng = np.random.RandomState(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
    if isinstance(prompt_lens, tuple) and len(prompt_lens) == 2:
        lens = rng.randint(prompt_lens[0], prompt_lens[1] + 1, n_requests)
    else:
        lens = rng.choice(np.asarray(list(prompt_lens)), n_requests)
    reqs = [
        Request(
            rid=i,
            prompt=rng.randint(0, vocab_size, size=int(lens[i])).astype(np.int32),
            max_new_tokens=max_new_tokens,
            arrival=float(arrivals[i]),
            sampling=S.SamplingParams(temperature=temperature),
            eos_id=eos_id,
        )
        for i in range(n_requests)
    ]
    if n_sessions is not None:
        for req, s in zip(reqs, rng.randint(0, n_sessions, n_requests)):
            req.session = f"s{int(s)}"
    return reqs
