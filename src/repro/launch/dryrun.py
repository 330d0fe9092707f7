import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(*ShapeDtypeStructs).compile()`` must
succeed on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh for
every assigned cell, and the compiled artifact yields the roofline terms
(cost_analysis + HLO collective-byte parsing).

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --all [--backend bine] [--out results/dryrun]
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import base as cfgbase
from repro.launch import hlo as H
from repro.launch.mesh import dp_axes as mesh_dp_axes, make_production_mesh
from jax import set_mesh


def sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def input_specs(arch: str, shape: str, mesh, backend: str = "bine",
                bucket_bytes: int = -1,
                tuning: str = "analytic",
                wire_dtype: str = "float32") -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, no device
    allocation) for every model input of the given cell, plus the step
    callable to lower.  Returns dict(step=fn, args=tuple_of_SDS, meta=...)."""
    from repro.models import transformer as T
    from repro.serve.engine import ServeConfig, cache_specs, make_serve_fns
    from repro.train.step import TrainConfig, for_mesh, make_train_step
    from repro.models.sharding import param_specs

    from repro.models import sharding as _sh

    cfg = cfgbase.get_config(arch)
    sc = cfgbase.SHAPES[shape]
    _sh.set_model_parallel(dict(zip(mesh.axis_names,
                                    mesh.devices.shape)).get("model", 1))
    dp = mesh_dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    B, S = sc.global_batch, sc.seq_len

    key = jax.random.key(0)
    params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
    pspecs = param_specs(cfg, params_shapes)

    def ns(s):
        return NamedSharding(mesh, s)

    params_sds = jax.tree.map(
        lambda l, s: sds(l.shape, l.dtype, ns(s)), params_shapes, pspecs)

    if sc.kind == "train":
        tcfg = for_mesh(TrainConfig(backend=backend, dp_axes=dp,
                                    bucket_bytes=bucket_bytes, tuning=tuning,
                                    wire_dtype=wire_dtype), mesh)
        step_fn, shardings, layout = make_train_step(cfg, tcfg, mesh,
                                                     params_shapes)
        state_shapes = jax.eval_shape(
            lambda p: _opt_shapes(cfg, tcfg, p, n_dp), params_shapes)
        state_sds = jax.tree.map(
            lambda l, s: sds(l.shape, l.dtype, s),
            state_shapes, shardings["state"])
        if cfg.frontend:
            inp = sds((B, S, cfg.frontend_dim), jnp.float32,
                      shardings["batch"]["inputs"])
        else:
            inp = sds((B, S), jnp.int32, shardings["batch"]["inputs"])
        batch_sds = {"inputs": inp,
                     "targets": sds((B, S), jnp.int32,
                                    shardings["batch"]["targets"])}
        plan = shardings.get("bucket_plan")
        from repro.train.step import bucket_report
        return {"step": step_fn, "args": (params_sds, state_sds, batch_sds),
                "kind": "train", "cfg": cfg, "shape": sc,
                "bucket_plan": plan.describe() if plan is not None else None,
                # per-bucket backend decisions + their table provenance
                # (measured vs analytic) — the tuner's end-to-end contract
                "bucket_decisions": bucket_report(tcfg, plan)}

    scfg = ServeConfig(dp_axes=dp, tuning=tuning)
    prefill_fn, decode_fn, shardings = make_serve_fns(cfg, scfg, mesh, B, S)
    bspec = P(dp if len(dp) > 1 else dp[0]) if B % n_dp == 0 else P()
    if sc.kind == "prefill":
        if cfg.frontend:
            inp = sds((B, S, cfg.frontend_dim), jnp.float32, ns(bspec))
        else:
            inp = sds((B, S), jnp.int32, ns(bspec))
        return {"step": prefill_fn, "args": (params_sds, inp),
                "kind": "prefill", "cfg": cfg, "shape": sc}

    # decode: one new token against a seq_len cache
    state_shapes = jax.eval_shape(
        lambda: T.init_decode_state(cfg, B, S))
    cspecs = cache_specs(cfg, scfg, B, S, mesh)
    state_sds = {
        "segments": [
            jax.tree.map(lambda l, s: sds(l.shape, l.dtype, ns(s)), seg, sp)
            for seg, sp in zip(state_shapes["segments"], cspecs["segments"])],
        "pos": sds((), jnp.int32, ns(P())),
    }
    if cfg.frontend:
        tok = sds((B, 1, cfg.frontend_dim), jnp.float32, ns(bspec))
    else:
        tok = sds((B, 1), jnp.int32, ns(bspec))
    return {"step": decode_fn, "args": (params_sds, state_sds, tok),
            "kind": "decode", "cfg": cfg, "shape": sc}


def _opt_shapes(cfg, tcfg, params, n_dp):
    from repro.optim.adamw import adamw_init_leaf
    from repro.train import zero
    layout = zero.zero_layout(cfg, params, n_dp)

    def one(p, zd):
        if zd < 0:
            return adamw_init_leaf(p)
        shp = list(p.shape)
        # global shape stays; sharding handles the split
        return {k: jnp.zeros(tuple(shp), jnp.float32)
                for k in ("master", "m", "v")}

    opt = jax.tree.map(one, params, layout)
    state = {"opt": opt, "step": jnp.zeros((), jnp.int32)}
    # int8-wire buckets carry a GLOBAL (n_dp, L) error-feedback residual
    from repro.train.step import _ef_init, resolve_bucket_plan
    ef = _ef_init(tcfg, resolve_bucket_plan(tcfg, n_dp, params, layout))
    if ef:
        state["ef"] = {bid: jnp.zeros((n_dp, v.shape[1]), jnp.float32)
                       for bid, v in ef.items()}
    return state


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def model_flops(cfg, sc) -> float:
    """6·N·D train / 2·N·D inference, N = active params, D = tokens."""
    n = cfg.n_active_params
    if sc.kind == "train":
        return 6.0 * n * sc.global_batch * sc.seq_len
    if sc.kind == "prefill":
        return 2.0 * n * sc.global_batch * sc.seq_len
    return 2.0 * n * sc.global_batch * 1  # decode: one token per request


def run_cell(arch: str, shape: str, multi_pod: bool, backend: str = "bine",
             verbose: bool = True, save_hlo: Optional[str] = None,
             bucket_bytes: int = -1,
             tuning: str = "analytic",
             wire_dtype: str = "float32") -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    pod = 256
    t0 = time.time()
    spec = input_specs(arch, shape, mesh, backend, bucket_bytes, tuning,
                       wire_dtype)
    with set_mesh(mesh):
        lowered = spec["step"].lower(*spec["args"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    if save_hlo:
        os.makedirs(os.path.dirname(save_hlo) or ".", exist_ok=True)
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())

    mem = compiled.memory_analysis()
    try:
        mem_d = {
            "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_in_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_in_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception:
        mem_d = {"repr": repr(mem)}

    roof = H.roofline_from_compiled(compiled, n_chips, pod)
    mf = model_flops(spec["cfg"], spec["shape"])
    out = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "backend": backend,
        "tuning": tuning,
        "wire_dtype": wire_dtype,
        "n_chips": n_chips,
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "memory": mem_d,
        "model_flops": mf,
        "useful_ratio": mf / roof.hlo_flops if roof.hlo_flops else None,
        "bucket_plan": spec.get("bucket_plan"),
        "bucket_decisions": spec.get("bucket_decisions"),
        **roof.as_dict(),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape} mesh={out['mesh']} backend={backend}")
        if spec.get("bucket_plan"):
            bp = spec["bucket_plan"]
            print(f"  grad buckets: {bp['n_buckets']} "
                  f"({bp['n_bucketed_leaves']} leaves packed, "
                  f"{bp['n_replicated_leaves']} replicated, "
                  f"cap={bp['capacity_bytes']}B)")
        for row in spec.get("bucket_decisions") or []:
            print(f"    bucket {row['bucket']}: "
                  f"rs={row['rs_backend']}/{row['rs_wire']} "
                  f"({row['rs_provenance']}, {row['rs_bytes']}B) "
                  f"ag={row['ag_backend']}/{row['ag_wire']} "
                  f"({row['ag_provenance']}, {row['ag_bytes']}B)")
        print(f"  memory_analysis: {mem_d}")
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  roofline: compute={roof.t_compute:.4f}s "
              f"memory={roof.t_memory:.4f}s collective={roof.t_collective:.4f}s"
              f" dominant={roof.dominant}")
        print(f"  collective bytes/chip={roof.coll_bytes_per_chip:.3e} "
              f"global(DCN)={roof.global_bytes_per_chip:.3e} "
              f"ops={roof.coll_op_counts}")
        print(f"  MODEL_FLOPS/HLO_FLOPS={out['useful_ratio'] and round(out['useful_ratio'], 3)}")
    return out


def runnable_cells():
    for arch in cfgbase.list_configs():
        for shape in cfgbase.SHAPES:
            if cfgbase.cell_is_runnable(arch, shape):
                yield arch, shape


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--backend", default="bine")
    ap.add_argument("--bucket-bytes", type=int, default=-1,
                    help="gradient-bucket capacity (wire bytes); "
                         "-1 = decision table, 0 = per-leaf collectives")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8", "auto"],
                    help="gradient/param wire compression (int8 = pow2-scale"
                         " codec + error feedback; auto = per-bucket table)")
    ap.add_argument("--tuning", default="analytic",
                    choices=["analytic", "measured"],
                    help="decision-table provenance for backend=auto: "
                         "'measured' merges the empirical tuner's table "
                         "(launch/tune.py) over the analytic one")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    cells = list(runnable_cells()) if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multi' if mp else 'single'}__{args.backend}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] skip existing {tag}")
                continue
            try:
                res = run_cell(arch, shape, mp, args.backend,
                               save_hlo=args.save_hlo,
                               bucket_bytes=args.bucket_bytes,
                               tuning=args.tuning,
                               wire_dtype=args.wire_dtype)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
            except Exception as e:
                traceback.print_exc()
                failures.append((tag, str(e)))
    if failures:
        print(f"FAILED {len(failures)} cells:")
        for t, e in failures:
            print(" ", t, e[:200])
        sys.exit(1)
    print("dry-run: all requested cells lowered + compiled OK")


if __name__ == "__main__":
    main()
