"""Training cells: the program's ZeRO-1 train step as ``launch/train.py``
builds it (``train.step.make_train_step`` over a ``data`` mesh of the
cell's chips, which applies ``for_mesh``), fed and synced as that
launcher's loop does: batch put, step, loss fetched every step.

Set-up makes the weights on the device from the seed, builds the step
and its state, and drives that same object through its first three steps
on the window's own feed (compiling it); the window then runs on.  Those
three steps are what ``correct`` compares with the plain reference:
each step's loss, each leaf's norm of the first clipped gradient (read
from Adam's first moment, ``m = (1 - b1) g``) and that gradient's
elements at a sample of positions drawn from the seed, and each leaf's
norm of the master weights' change after three steps.
"""

from __future__ import annotations

import gc
import math
import queue
import threading
import time

import numpy as np

from bench import core
from bench.model import model_config, tree_shapes, weights_fn

#: seconds of the window a traced run records
TRACE_S = 4.0
#: elements of each leaf's first gradient compared one by one
SAMPLE = 1 << 16


class Feed:
    """Host batches made ahead on one thread, as ``train.data.Prefetcher``
    does for the launcher."""

    def __init__(self, batch_at, depth: int = 2):
        self.batch_at = batch_at
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.stop = threading.Event()
        self.t = threading.Thread(target=self._run, daemon=True)
        self.t.start()

    def _run(self):
        i = 0
        while not self.stop.is_set():
            b = self.batch_at(i)
            while not self.stop.is_set():
                try:
                    self.q.put(b, timeout=0.1)
                    break
                except queue.Full:
                    pass
            i += 1

    def next(self):
        return self.q.get()

    def close(self):
        self.stop.set()
        self.t.join()


def gap(prog, ref, floor=None):
    """Worst leaf: |prog - ref| against the larger of the reference's
    norm of that leaf and of the median leaf."""
    med = float(np.median(ref))
    keep = range(len(ref)) if floor is None else floor
    return max(abs(p - r) / max(r, med, 1e-30)
               for i, (p, r) in enumerate(zip(prog, ref)) if i in keep)


def moved_leaves(ref_grads):
    """Leaves whose reference gradient is above a thousandth of the
    median leaf's: the others move under Adam by round-off alone."""
    med = float(np.median(ref_grads))
    return {i for i, g in enumerate(ref_grads) if g >= 1e-3 * med}


def elem_gap(prog: dict, ref: dict) -> float:
    """Worst leaf: the norm of the first gradient's error, estimated from
    its sampled elements, against the larger of the reference's norm of
    that leaf and of the median leaf.  Unlike a gap of norms, it does not
    average rounding out."""
    med = float(np.median(ref["grad_norms"]))
    worst = 0.0
    for n, p, r, size in zip(ref["grad_norms"], prog["grad_sample"],
                             ref["grad_sample"], prog["sizes"]):
        err = float(np.linalg.norm(np.asarray(p, np.float64) - r)) \
            * math.sqrt(size / len(r))
        worst = max(worst, err / max(n, med, 1e-30))
    return worst


def readings(prog: dict, ref: dict) -> dict:
    """Each number compared: the worst step's relative loss gap, the
    first step's, and the worst leaf's gaps of the first clipped
    gradient's norm, of its elements and of the master weights'
    change."""
    rel = [abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                 ref["losses"])]
    return {"loss_gap": max(rel), "loss1_gap": rel[0],
            "grad_norm_gap": gap(prog["grad_norms"], ref["grad_norms"]),
            "grad_elem_gap": elem_gap(prog, ref),
            "change_gap": gap(prog["change_norms"], ref["change_norms"],
                              moved_leaves(ref["grad_norms"]))}


def compare(prog: dict, ref: dict, limits: dict):
    """(name, reading, limit) of each number the cell holds a limit for."""
    got = readings(prog, ref)
    return [(n, got[n], lim) for n, lim in limits.items()]


def sample_index(c: dict, seed: int):
    """Flat positions in each leaf, drawn from the seed: ``SAMPLE`` of
    them, or every element of a smaller leaf."""
    import jax
    rng = np.random.default_rng(core.seed32(seed, 3))
    out = []
    for x in jax.tree.leaves(tree_shapes(c)):
        n = math.prod(x.shape)
        out.append(np.arange(n, dtype=np.int32) if n <= SAMPLE
                   else rng.integers(0, n, SAMPLE).astype(np.int32))
    return out


def build(run: core.Run):
    """The program's step and its state's initializer over the cell's
    chips."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.train.step import TrainConfig, make_init_fns, make_train_step

    tw = run.workload["train"]
    devices = jax.devices()[:run.chips]
    mesh = make_mesh((run.chips, 1), ("data", "model"), devices)
    mcfg = model_config(run.config, z_loss=tw["z_loss"])
    tcfg = TrainConfig(backend=tw["backend"], dp_axes=("data",),
                       wire_dtype=tw["wire_dtype"], clip_norm=tw["clip_norm"],
                       adamw=AdamWConfig(**tw["adamw"]))
    shapes = tree_shapes(run.config)
    step, shardings, _ = make_train_step(mcfg, tcfg, mesh, shapes)
    _, init_state = make_init_fns(mcfg, tcfg, mesh, shapes)
    return mesh, devices, step, shardings, init_state


def program_readings(run, step, shardings, init_state, feed, n_steps=3,
                     diagnose=False):
    """Make the weights and state from the seed and take the first
    ``n_steps`` steps; returns the readings and the live object.
    ``diagnose`` adds each leaf's change after every step."""
    import jax
    import jax.numpy as jnp

    b1 = run.workload["train"]["adamw"]["b1"]
    s32 = core.seed32(run.seed)
    tdef = jax.tree.structure(tree_shapes(run.config))
    make = weights_fn(run.config)
    params = jax.device_put(make(s32), shardings["params"])
    state = init_state(params)

    shapes = jax.tree.leaves(tree_shapes(run.config))
    idx = [jnp.asarray(i) for i in sample_index(run.config, run.seed)]

    @jax.jit
    def first_grad(opt, idx):
        """Each leaf's norm and sampled elements, from ``m = (1 - b1) g``
        (the positions are an argument: as constants they would make a
        new program for every seed)."""
        ms = [o["m"] for o in tdef.flatten_up_to(opt)]
        assert [m.shape for m in ms] == [x.shape for x in shapes]
        return ([jnp.linalg.norm(m) / (1 - b1) for m in ms],
                [m.reshape(-1)[i] / (1 - b1) for m, i in zip(ms, idx)])
    change_norms = jax.jit(lambda opt, p0: [
        jnp.linalg.norm(o["master"] - p.astype(jnp.float32))
        for o, p in zip(tdef.flatten_up_to(opt), jax.tree.leaves(p0))])
    losses, g, gs, by_step = [], None, None, []
    for i in range(n_steps):
        params, state, loss = one_step(step, shardings, feed, params, state)
        losses.append(loss)
        if i == 0:
            norms, elems = first_grad(state["opt"], idx)
            g = [float(x) for x in norms]
            gs = [np.asarray(x) for x in elems]
        if diagnose or i == n_steps - 1:
            by_step.append([float(x) for x in change_norms(
                state["opt"], make(s32))])
    out = {"losses": losses, "grad_norms": g, "grad_sample": gs,
           "sizes": [math.prod(x.shape) for x in shapes],
           "change_norms": by_step[-1]}
    if diagnose:
        out["change_by_step"] = by_step
    return out, params, state


def one_step(step, shardings, feed, params, state):
    import jax
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("bench.put"):
        b = feed.next()
        batch = {k: jax.device_put(v, shardings["batch"][k])
                 for k, v in b.items()}
    with TraceAnnotation("bench.step"):
        params, state, metrics = step(params, state, batch)
    with TraceAnnotation("bench.fetch"):
        loss = float(metrics["loss"])
    return params, state, loss


def reference(run, mesh_devices, mode="f32", fault="", diagnose=False):
    import jax
    from jax.sharding import Mesh
    from bench import reference as R

    tw = run.workload["train"]
    mesh = Mesh(np.asarray(mesh_devices), ("x",))
    B = run.chips * tw["per_chip_batch"]
    batch_at = core.load_module("traffic", run.traffic["generator"]).make(
        run.traffic, core.seed32(run.seed, 1), B, run.config["vocab_size"])
    s32 = core.seed32(run.seed)
    make = lambda: weights_fn(run.config)(s32)  # noqa: E731
    out = R.train_readings(run.config, tw, make,
                           [batch_at(i) for i in range(3)], mesh,
                           mode=mode, fault=fault, diagnose=diagnose,
                           sample=sample_index(run.config, run.seed))
    out["sizes"] = [math.prod(x.shape)
                    for x in jax.tree.leaves(tree_shapes(run.config))]
    return out


def run_cell(run: core.Run) -> None:
    import jax

    tw = run.workload["train"]
    mesh, devices, step, shardings, init_state = build(run)
    B = run.chips * tw["per_chip_batch"]
    T = run.traffic["seq_len"]
    batch_at = core.load_module("traffic", run.traffic["generator"]).make(
        run.traffic, core.seed32(run.seed, 1), B, run.config["vocab_size"])
    feed = Feed(batch_at)
    try:
        with jax.set_mesh(mesh):
            prog, params, state = program_readings(
                run, step, shardings, init_state, feed)
            run.setup_s = time.perf_counter() - run.t_start
            # a traced run records its first TRACE_S seconds and stops
            tracer = core.WindowTrace(0.0, TRACE_S) if run.trace else None
            t0 = time.perf_counter()
            while True:
                ts = time.perf_counter()
                if tracer:
                    tracer.tick(ts - t0)
                    if not tracer.pending:
                        break
                elif ts - t0 >= run.seconds:
                    break
                params, state, loss = one_step(step, shardings, feed,
                                               params, state)
                run.steps.append((ts, time.perf_counter(), B * T))
                run.attempted += 1
                run.failed += 0 if math.isfinite(loss) else 1
            run.window = (t0, ts)
            if tracer:
                run.trace_summary = tracer.finish()
                run.extra["trace_host_window"] = tracer.host
            run.device["memory_peak_bytes"] = core.memory_peak(devices)
    finally:
        feed.close()
    del params, state, step
    gc.collect()
    ref = reference(run, devices)
    run.extra["readings"] = {"program": prog, "reference": ref}
    run.checks = compare(prog, ref, run.workload["limits"])
