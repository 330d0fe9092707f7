"""Fault-tolerance runtime: failure detection, restart, elastic re-mesh,
straggler monitoring.

On a real multi-pod deployment the failure signal comes from the runtime
(missing heartbeats / XLA errors); here the same control flow is driven by
an injectable ``FailureInjector`` so the restart & elastic paths are
actually exercised by tests:

  * ``TrainLoop`` — step loop with async checkpoints, catches
    ``DeviceFailure``, restores from the latest checkpoint and resumes;
  * elastic re-mesh — on "permanent" failures, rebuild the mesh from the
    surviving device count (halve the data axis), recompute the ZeRO
    layout for the new n_dp, and reshard the restored state;
  * ``StragglerMonitor`` — per-step wall-time EWMA; flags outliers (on a
    real pod this triggers hot-spare swap; here it feeds metrics/logs).

Step times are ``time.perf_counter()`` readings; each step's put, step
and fetch are ``train.put``/``train.step``/``train.fetch`` spans for a
profiler (``repro.obs.timeline.host_span``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import timeline as obs_timeline
from repro.obs.timeline import host_span
from repro.train import checkpoint as ckpt


class DeviceFailure(RuntimeError):
    """Simulated device/pod failure; ``permanent`` drives elastic re-mesh."""

    def __init__(self, msg: str, permanent: bool = False):
        super().__init__(msg)
        self.permanent = permanent


@dataclass
class FailureInjector:
    """Deterministic failure schedule: {step: permanent?}."""
    schedule: Dict[int, bool] = field(default_factory=dict)
    fired: set = field(default_factory=set)

    def check(self, step: int):
        if step in self.schedule and step not in self.fired:
            self.fired.add(step)
            raise DeviceFailure(f"injected failure at step {step}",
                                permanent=self.schedule[step])


@dataclass
class StragglerMonitor:
    """EWMA of step wall-time; flags steps slower than ratio x the mean."""
    alpha: float = 0.2
    ratio: float = 2.0
    warmup: int = 3
    ewma: Optional[float] = None
    seen: int = 0
    flagged: List[Tuple[int, float, float]] = field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        self.seen += 1
        if self.ewma is None:
            self.ewma = dt
            return False
        is_straggler = (self.seen > self.warmup and dt > self.ratio * self.ewma)
        if is_straggler:
            self.flagged.append((step, dt, self.ewma))
        # EWMA excludes flagged outliers so one straggler can't mask the next
        if not is_straggler:
            self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return is_straggler


@dataclass
class TrainLoopConfig:
    total_steps: int
    ckpt_every: int = 10
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    max_restarts: int = 8


class TrainLoop:
    """Restartable training loop.

    ``build`` is a factory: build(n_data_shrink: int) ->
      (step_fn, init_params_fn, init_state_fn, put_batch_fn, data_iter_fn)
    so an elastic restart can rebuild everything on a smaller mesh.
    """

    def __init__(self, cfg: TrainLoopConfig, build: Callable,
                 injector: Optional[FailureInjector] = None):
        self.cfg = cfg
        self.build = build
        self.injector = injector or FailureInjector()
        self.monitor = StragglerMonitor()
        self.restarts = 0
        self.shrink = 0        # times the data axis was halved (elastic)
        self.history: List[Dict[str, float]] = []

    def run(self, key) -> Dict[str, Any]:
        cpr = ckpt.AsyncCheckpointer(self.cfg.ckpt_dir, keep=self.cfg.keep)
        step_fn, init_p, init_s, put_batch, data_at = self.build(self.shrink)
        params = init_p(key)
        state = init_s(params)
        start = 0
        latest = ckpt.latest_step(self.cfg.ckpt_dir)
        if latest is not None:
            params, state = self._restore(latest, params, state)
            start = latest
        s = start
        while s < self.cfg.total_steps:
            try:
                self.injector.check(s)
                t0 = time.perf_counter()
                with host_span("train.put"):
                    batch = put_batch(data_at(s))
                with host_span("train.step"):
                    params, state, metrics = step_fn(params, state, batch)
                with host_span("train.fetch"):
                    loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                self.monitor.observe(s, dt)
                self.history.append({"step": s, "loss": loss, "dt": dt,
                                     "restarts": self.restarts,
                                     "shrink": self.shrink})
                if obs_metrics.enabled():
                    obs_metrics.get_registry().observe(
                        "train_step_seconds", dt, shrink=self.shrink)
                    obs_timeline.get_timeline().span(
                        "train_step", "train", t0 * 1e6, dt * 1e6,
                        step=s, loss=loss, restarts=self.restarts)
                s += 1
                if s % self.cfg.ckpt_every == 0 or s == self.cfg.total_steps:
                    cpr.save(s, {"params": params, "state": state},
                             extra={"step": s})
            except DeviceFailure as e:
                self.restarts += 1
                if self.restarts > self.cfg.max_restarts:
                    raise
                cpr.wait()
                if e.permanent:
                    self.shrink += 1  # lose half the data axis; re-mesh
                step_fn, init_p, init_s, put_batch, data_at = self.build(
                    self.shrink)
                params = init_p(key)
                state = init_s(params)
                latest = ckpt.latest_step(self.cfg.ckpt_dir)
                if latest is not None:
                    params, state = self._restore(latest, params, state)
                    s = latest
                else:
                    s = 0
        cpr.wait()
        return {"history": self.history, "restarts": self.restarts,
                "shrink": self.shrink,
                "stragglers": list(self.monitor.flagged)}

    def _restore(self, step: int, params_like, state_like):
        tree = ckpt.restore(self.cfg.ckpt_dir, step,
                            {"params": params_like, "state": state_like})
        return tree["params"], tree["state"]
