#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one
process (set-up is paid once):

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
      --control-seeds 7,8,9

For each of ``--seeds``: the program's numbers against the plain
reference, through the same timed path as a run (training: the first
three steps of the step object; serving: a short window of the cell's
own traffic, long enough to finish its longest requests).  For each of
``--control-seeds``: the control against the reference, and for training
cells the planted fault (half the batch left out).  The control is the
reference computed with int8 matrix products (training: in both passes)
in the program's place.  One JSON line per reading; the limit goes above
the largest program reading and below the smallest control or fault
reading (``PERF.md`` gives both).  The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import core  # noqa: E402

#: seconds of traffic in a serving reading; every request then runs to
#: its end, the longest (256 tokens) included
SERVE_S = 10.0


def emit(**kw):
    print(json.dumps(kw), flush=True)


def train(args, cell):
    import jax
    from bench.kinds import train as K
    w = cell["workload"]
    B = w["chips"] * w["train"]["per_chip_batch"]

    def program(built, seed, look=False):
        mesh, devices, step, shardings, init_state = built
        run = make_run(args, cell, seed)
        batch_at = core.load_module("traffic", cell["traffic"]["generator"]
                                    ).make(cell["traffic"],
                                           core.seed32(seed, 1), B,
                                           cell["config"]["vocab_size"])
        feed = K.Feed(batch_at)
        try:
            with jax.set_mesh(mesh):
                prog, params, state = K.program_readings(
                    run, step, shardings, init_state, feed, diagnose=look)
        finally:
            feed.close()
        del params, state
        gc.collect()
        return run, prog

    built = K.build(make_run(args, cell, 0))
    devices = built[1]
    for seed in args.seeds:
        t = time.perf_counter()
        look = seed == args.seeds[0]
        run, prog = program(built, seed, look)
        ref = K.reference(run, devices, diagnose=look)
        emit(seed=seed, what="program", s=time.perf_counter() - t,
             **K.readings(prog, ref))
        if look:
            emit(seed=seed, what="look", **{
                k: {n: v for n, v in d.items() if n != "grad_sample"}
                for k, d in (("program", prog), ("reference", ref))})
    for seed in args.control_seeds:
        run = make_run(args, cell, seed)
        ref = K.reference(run, devices)
        for what, mode, fault in (("control", "int8", ""),
                                  ("half_batch", "f32", "half")):
            emit(seed=seed, what=what, **K.readings(
                K.reference(run, devices, mode=mode, fault=fault), ref))


def serve(args, cell):
    import jax
    from bench.kinds import serve as K
    from repro.serve.scheduler import ContinuousBatchingScheduler, Request
    w = cell["workload"]
    seeds = [(s, False) for s in args.seeds] + \
        [(s, True) for s in args.control_seeds]
    run0 = make_run(args, cell, seeds[0][0])
    mesh, devices, mcfg, fns, S = K.build(run0)
    for seed, control in seeds:
        run = make_run(args, cell, seed)
        run.seconds = SERVE_S
        with jax.set_mesh(mesh):
            params = K.weights_fn(run.config)(core.seed32(seed))
            sched = K.stamped_scheduler(run, ContinuousBatchingScheduler,
                                        mcfg, fns, params,
                                        w["serve"]["slots"], S)
            K.instrument(run, fns, sched)
            _, live = K.serve_window(run, sched, K.make_requests(run),
                                     Request)
            while sched.step():
                pass
        pick = K.sample_finished(seed, [q for q in live if q.finished])
        seqs = [(q.prompt, list(q.generated)) for q in pick]
        del sched, params
        gc.collect()
        gaps = K.reference_gaps(run, seqs)
        tokens = int(sum(len(g) for g in gaps))
        emit(seed=seed, what="program", tokens=tokens,
             logit_gap=max(float(g.max()) for g in gaps))
        if control:
            gaps = K.reference_gaps(run, seqs, mode="int8")
            emit(seed=seed, what="control", tokens=tokens,
                 logit_gap=max(float(g.max()) for g in gaps))


def make_run(args, cell, seed):
    w = cell["workload"]
    run = core.Run(cell=args.workload, workload=w, config=cell["config"],
                   traffic=cell["traffic"], seed=seed, seconds=SERVE_S,
                   trace=False, chips=w["chips"])
    run.peaks = core.load_peaks(args.device["kind"])
    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    args.seeds = [int(s) for s in args.seeds.split(",") if s]
    args.control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    cell = core.load_cell(args.workload)
    args.device = core.require_chips(cell["workload"]["chips"])
    core.enable_cache()
    t = time.perf_counter()
    {"train": train, "serve": serve}[cell["workload"]["kind"]](args, cell)
    core.log(f"calibration took {time.perf_counter() - t:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
