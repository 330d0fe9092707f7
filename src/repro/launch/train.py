"""End-to-end training driver.

Runs on whatever devices exist (CPU host devices for local runs; the
production meshes on real pods).  Wires together: synthetic data pipeline,
Bine gradient collectives, ZeRO-1 optimizer, async checkpointing, the
straggler monitor, and restart-on-failure.

  python -m repro.launch.train --arch phi4-mini-3.8b --reduced \\
      --mesh 1,2,4 --steps 200 --batch 8 --seq 128 --backend bine

``--layers N`` cuts depth only (every width stays as published), so a
full-width model fits one chip's memory: phi4-mini-3.8b at one layer is
about 715 M parameters, 11.4 GB at 16 bytes a parameter.

Each step's batch put, step and loss fetch are ``train.put``,
``train.step`` and ``train.fetch`` spans; ``jax.profiler.trace`` around
:func:`main` records them on the device trace's clock.
"""

from __future__ import annotations

import argparse
import math
import time

import jax
import numpy as np

from repro.configs import base as cfgbase
from repro.launch.mesh import (dp_axes as mesh_dp_axes, enable_compile_cache,
                               mesh_from_arg)
from repro.models import transformer as T
from repro.obs.timeline import host_span
from repro.optim.adamw import AdamWConfig
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, Prefetcher, make_batch
from repro.train.runtime import StragglerMonitor
from repro.train.step import TrainConfig, make_init_fns, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep the first N layers (depth cut; widths as "
                         "published); 0 keeps all")
    ap.add_argument("--mesh", default="",
                    help="pod,data,model (default: all devices on data)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--backend", default="bine",
                    choices=["bine", "recdoub", "ring", "xla", "bine_hier",
                             "pallas_fused", "auto"])
    ap.add_argument("--topology", default="tpu_multipod",
                    help="decision-table preset for --backend auto")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8", "auto"],
                    help="gradient/param wire compression; int8 = pow2-scale "
                         "wire codec with error feedback (bucketed path), "
                         "auto = per-bucket (backend, wire) table lookup")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = cfgbase.get_config(args.arch)
    if args.reduced:
        cfg = cfgbase.reduced(cfg)
    if args.layers:
        if not 0 < args.layers <= cfg.n_layers:
            raise SystemExit(f"--layers must be in 1..{cfg.n_layers}, "
                             f"got {args.layers}")
        cfg = cfg.replace(n_layers=args.layers)

    mesh = mesh_from_arg(args.mesh)
    dp_axes = mesh_dp_axes(mesh)

    acfg = AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                       total_steps=args.steps)
    tcfg = TrainConfig(backend=args.backend, dp_axes=dp_axes,
                       accum_steps=args.accum, adamw=acfg,
                       wire_dtype=args.wire_dtype, topology=args.topology)

    key = jax.random.key(args.seed)
    params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_shapes))
    print(f"[train] arch={cfg.name} params={n_params:,} mesh={dict(mesh.shape)} "
          f"backend={args.backend} dp={dp_axes}")

    step_fn, shardings, layout = make_train_step(cfg, tcfg, mesh, params_shapes)
    init_p, init_s = make_init_fns(cfg, tcfg, mesh, params_shapes)

    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq,
                      vocab_size=cfg.vocab_size,
                      frontend_dim=cfg.frontend_dim if cfg.frontend else 0,
                      seed=args.seed + 1)

    cpr = ckpt.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    monitor = StragglerMonitor()

    with jax.set_mesh(mesh):
        params = init_p(key)
        state = init_s(params)
        start = 0
        if args.resume and args.ckpt_dir:
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest is not None:
                tree = ckpt.restore(args.ckpt_dir, latest,
                                    {"params": params, "state": state})
                params, state = tree["params"], tree["state"]
                start = latest
                print(f"[train] resumed from step {start}")

        pf = Prefetcher(dcfg, start_step=start)
        losses, gnorms, step_s = [], [], []
        try:
            t_all = time.perf_counter()
            for s in range(start, args.steps):
                t0 = time.perf_counter()
                with host_span("train.put"):
                    _, b = pf.next()
                    batch = {k: jax.device_put(v, shardings["batch"][k])
                             for k, v in b.items()}
                with host_span("train.step"):
                    params, state, metrics = step_fn(params, state, batch)
                with host_span("train.fetch"):
                    loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                if not math.isfinite(loss):
                    raise FloatingPointError(f"step {s}: loss {loss}")
                losses.append(loss)
                gnorms.append(float(metrics["grad_norm"]))
                step_s.append(dt)
                if monitor.observe(s, dt):
                    print(f"[straggler] step {s} took {dt:.3f}s "
                          f"(ewma {monitor.ewma:.3f}s)")
                if s % args.log_every == 0 or s == args.steps - 1:
                    print(f"step {s:5d} loss {loss:.4f} "
                          f"gnorm {gnorms[-1]:.3f} "
                          f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
                if cpr and (s + 1) % args.ckpt_every == 0:
                    cpr.save(s + 1, {"params": params, "state": state})
            if cpr:
                cpr.save(args.steps, {"params": params, "state": state},
                         block=True)
            total = time.perf_counter() - t_all
            print(f"[train] done: {args.steps - start} steps in {total:.1f}s "
                  f"({(args.steps - start) / max(total, 1e-9):.2f} it/s); "
                  f"stragglers flagged: {len(monitor.flagged)}")
        finally:
            pf.close()
    return {"arch": cfg.name, "n_layers": cfg.n_layers, "n_params": n_params,
            "losses": losses, "grad_norms": gnorms, "step_s": step_s,
            "tokens_per_step": args.batch * args.seq, "params": params}


if __name__ == "__main__":
    main()
