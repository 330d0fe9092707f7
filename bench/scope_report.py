#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, whose
trace is also reduced by the program's named scopes and host spans
(``bench/scopes.py``).

  python3 bench/scope_report.py --workload <cell> --seed <n> \
      --seconds <s> [--out <file.json>] [--keep-trace <file.xplane.pb.gz>]

Prints ``run.py``'s result line, then one JSON line, also written to
``--out``: each module's device ms a run by scope and the part of it
between ops, the heaviest ops with their scope and ``op_name`` path,
idle gaps by the innermost host span (the program's beside the
benchmark's), and what tracing cost the host (decode calls inside the
traced slice against those before it; a train run's rate inside it).
``--keep-trace`` also keeps the raw trace, gzipped, where it is under
15 MB, to reduce it again by hand (``scopes.summarize`` of its gunzipped
bytes).  The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import statistics
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import core, run as bench_run, scopes, trace  # noqa: E402

#: the largest gzipped trace ``--keep-trace`` keeps
KEEP_MAX = 15 << 20
#: per-layer readings of the program's scopes: (module, scope)
SCOPE_METRICS = {
    "decode_attn_ms.serve": ("decode_slots", "decode_attn"),
    "kv_write_ms.serve": ("decode_slots", "kv_write"),
    "attn_ms.train": ("stepped", "attn"),
    "embed_ms.train": ("stepped", "embed"),
}
MODULES = ("decode_slots", "insert", "stepped")


def scope_report(s: scopes.ScopeSummary) -> dict:
    n = max(s.n_devices, 1)
    mods = {}
    for frag in MODULES:
        runs = sum(v for k, v in s.module_runs.items() if frag in k)
        if not runs:
            continue
        ns = sum(v for k, v in s.module_ns.items() if frag in k)
        mods[frag] = {
            "runs": runs, "device_ms_per_run": ns / runs / 1e6,
            "scopes_ms_per_run": {k: v / runs / 1e6 for k, v in sorted(
                s.scopes(frag).items(), key=lambda kv: -kv[1])},
            "gap_pct": 100.0 * s.module_gap_ns(frag) / ns if ns else None}
    top = sorted(s.ops.items(), key=lambda kv: -kv[1][2])[:15]
    return {
        "metrics": {m: s.per_run_ms(*where)
                    for m, where in SCOPE_METRICS.items()},
        "modules": mods,
        "top_ops": [[mod, op, sc, path, ns / n * 1e-6]
                    for (mod, op), (sc, path, ns) in top],
        "idle_gaps": sorted(s.idle_by_span.items(), key=lambda kv: -kv[1]),
    }


def tracing_cost(run: core.Run) -> dict:
    """Host time of the work inside the traced slice against outside."""
    lo, hi = run.extra.get("trace_host_window", (None, None))
    if lo is None:
        return {}
    if run.decode_calls:
        def ms(calls):
            return statistics.mean(1e3 * (e - s) for s, e, _, _ in calls) \
                if calls else None
        return {"decode_call_ms_before": ms(
                    [c for c in run.decode_calls if c[0] < lo]),
                "decode_call_ms_traced": ms(
                    [c for c in run.decode_calls if lo <= c[0] <= hi])}
    steps = [st for st in run.steps if lo <= st[0] <= hi]
    if not steps:
        return {}
    return {"traced_tokens_per_s": sum(t for _, _, t in steps)
            / (steps[-1][1] - steps[0][0])}


def keep(raw: bytes, out: str) -> None:
    data = gzip.compress(raw, compresslevel=6)
    if len(data) <= KEEP_MAX:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "wb") as f:
            f.write(data)
    core.log(f"trace {len(data)} bytes gzipped"
             f"{'' if len(data) <= KEEP_MAX else ', not kept'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args(argv)
    manifest = core.load_manifest()
    chips = core.load_cell(args.workload)["workload"]["chips"]
    device = core.require_chips(chips)
    core.enable_cache()
    report = {}

    def stop_trace(path):
        """``core.stop_trace``, with the scope reduction of the same
        trace."""
        import jax
        jax.profiler.stop_trace()
        try:
            data = scopes.load(path)
            if args.keep_trace:
                keep(data, args.keep_trace)
            pd = jax.profiler.ProfileData.from_serialized_xspace(data)
            try:
                report.update(scope_report(scopes.reduce(
                    *scopes.read_planes(pd), scopes.metadata_paths(data))))
            except Exception:   # the run's own result line still prints
                report["scope_error"] = traceback.format_exc()
            return trace.reduce(*trace.read_planes(pd))
        finally:
            shutil.rmtree(path, ignore_errors=True)

    core.stop_trace = stop_trace
    args.trace = 1
    run = bench_run.execute(args, device, manifest)
    metrics = bench_run.read_metrics(run, manifest)
    print(core.result_line(run, metrics, run.trace_summary.breakdown()),
          flush=True)
    report.update(cell=args.workload, seed=args.seed,
                  tracing_cost=tracing_cost(run))
    line = json.dumps(report)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
