#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Sets up the cell (weights from the seed, compile or compile cache,
warm-up of the cell's own shapes), measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints one
JSON line last: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window), ``device``, with ``--trace 1``
``breakdown``, and ``checks``, each number compared beside its limit.
The checks are also the last lines on standard error.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import core  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default="",
                    help="with --trace 1, also write the raw trace's planes, "
                         "lines and heaviest events to this JSON file, to "
                         "look at a trace by hand")
    return ap.parse_args(argv)


def read_metrics(run: core.Run, manifest: dict) -> dict:
    """Each metric of the cell from its reader; a reader that finds
    nothing to read leaves its metric out."""
    out = {}
    for m in core.metrics_for(manifest, run.cell, per_layer=run.trace):
        value = core.load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(args, device: dict, manifest: dict) -> core.Run:
    """Set up, measure and check one run of ``args.workload`` on the
    devices JAX sees; ``device`` names them."""
    cell = core.load_cell(args.workload)
    w = cell["workload"]
    run = core.Run(cell=args.workload, workload=w, config=cell["config"],
                   traffic=cell["traffic"], seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   chips=w["chips"], t_start=T_START)
    run.device = dict(device)
    run.peaks = core.load_peaks(device["kind"])
    core.load_module("kinds", w["kind"]).run_cell(run)
    if run.trace:
        s = run.trace_summary
        run.device["busy_s"] = s.busy_s
        run.device["window_s"] = s.window_s
    return run


def main(argv=None) -> int:
    args = parse(argv)
    manifest = core.load_manifest()
    chips = core.load_cell(args.workload)["workload"]["chips"]
    device = core.require_chips(chips)
    core.enable_cache()
    core.DUMP_TRACE = args.dump_trace or None
    run = execute(args, device, manifest)
    metrics = read_metrics(run, manifest)
    breakdown = run.trace_summary.breakdown() if run.trace else None
    for k, v in run.extra.items():
        core.log(f"{k}: {v}")
    for name, value, limit in run.checks:
        core.log(f"check {name} {value!r} limit {limit!r}")
    print(core.result_line(run, metrics, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
