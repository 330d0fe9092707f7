#!/usr/bin/env python3
"""Find a serving cell's knee on the chip: the highest open-loop rate at
which time to first token does not grow over the window.

  python3 bench/knee.py --workload serve.phi4-mini.chat --seconds 30 \
      --rates 3,3.5,4

One process: the cell is set up once, then each rate runs the cell's
traffic mix at that rate for ``--seconds`` and drains.  One JSON line a
rate: requests, tokens served a second, the median time to first token
of the first and of the second half of the arrivals (a second half well
above the first means the queue grows), its 95th percentile, and the
gaps between tokens.  The benchmark's runs never run this; the cell's
mix holds the rate chosen from it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = core.load_cell(args.workload)
    core.require_chips(cell["workload"]["chips"])
    core.enable_cache()

    import jax
    from bench.kinds import serve as K
    from repro.serve.scheduler import ContinuousBatchingScheduler, Request
    run = core.Run(cell=args.workload, workload=cell["workload"],
                   config=cell["config"], traffic=cell["traffic"], seed=0,
                   seconds=args.seconds, trace=False, chips=1)
    mesh, _, mcfg, fns, S = K.build(run)
    gen = core.load_module("traffic", run.traffic["generator"])
    with jax.set_mesh(mesh):
        params = K.weights_fn(run.config)(0)
        sched = K.stamped_scheduler(run, ContinuousBatchingScheduler, mcfg,
                                    fns, params,
                                    run.workload["serve"]["slots"], S)
        K.instrument(run, fns, sched)
        first = gen.make(run.traffic, 0, 1.0, run.config["vocab_size"])[0]
        K.warm_up(sched, Request, first["prompt"])
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(run.traffic, rate_per_s=rate)
            reqs = gen.make(mix, k, args.seconds, run.config["vocab_size"])
            t0, live = K.serve_window(run, sched, reqs, Request)
            while sched.step():
                pass
            hi = t0 + args.seconds
            ttft = [q.stamps[0] - t0 - q.arrival for q in live]
            half = len(ttft) // 2
            gaps = [b - a for q in live
                    for a, b in zip(q.stamps, q.stamps[1:]) if b <= hi]
            print(json.dumps({
                "rate": rate, "requests": len(live),
                "tokens_per_s": sum(t0 <= t <= hi for q in live
                                    for t in q.stamps) / args.seconds,
                "ttft_median_s": [statistics.median(ttft[:half]),
                                  statistics.median(ttft[half:])],
                "ttft_p95_s": core.percentile(ttft, 95),
                "itl_p95_s": core.percentile(gaps, 95)}), flush=True)
    core.log(f"knee sweep took {time.perf_counter() - run.t_start:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
