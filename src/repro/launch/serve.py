"""Continuous-batching serving driver: a Poisson arrival trace of
mixed-length requests through the paged-KV scheduler.

  python -m repro.launch.serve --arch gemma3-4b --reduced --mesh 2,4 \\
      --slots 4 --requests 16 --rate 0.5 --max-new 32

Each request prefills into a free KV page (one compile covers every
prompt length), decodes interleaved with whatever else is running, and
retires on EOS or its token budget, recycling the page.  ``--backend
auto`` consults the topology decision table for the serving collective
plan; ``--backend xla`` pins the GSPMD defaults.

Architectures the pool cannot serve (recurrent blocks, MoE capacity
dispatch, modality frontends — see ``engine.pool_supported``) fall back
to the legacy fixed-batch loop: one lock-step batch of ``--slots``
same-length prompts, decoded for ``--max-new`` tokens.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax import set_mesh

from repro.configs import base as cfgbase
from repro.launch.mesh import (dp_axes as mesh_dp_axes, enable_compile_cache,
                               mesh_from_arg)
from repro.models import transformer as T
from repro.serve.engine import ServeConfig, make_serve_fns, page_len
from repro.serve.scheduler import ContinuousBatchingScheduler, poisson_trace


def run_fixed_batch(cfg, fns, params, mesh, batch, prompt_len, max_new,
                    seed=0):
    """Legacy lock-step prefill+decode for archs the pool cannot serve
    (recurrent/MoE/frontend).  Shared by this CLI and the example."""
    rng = np.random.RandomState(seed)
    B, L = batch, prompt_len
    if cfg.frontend:
        prompt = jnp.asarray(rng.randn(B, L, cfg.frontend_dim), jnp.float32)
    else:
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(B, L)),
                             jnp.int32)
    with set_mesh(mesh):
        t0 = time.time()
        logits, state = fns.prefill(params, prompt)
        jax.block_until_ready(logits)
        print(f"[serve] fixed-batch prefill {B}x{L}: "
              f"{(time.time()-t0)*1e3:.0f}ms")
        toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        outs = [np.asarray(toks)]
        t0 = time.time()
        for _ in range(max_new - 1):
            step_in = (jnp.asarray(rng.randn(B, 1, cfg.frontend_dim),
                                   jnp.float32) if cfg.frontend else toks)
            logits, state = fns.decode(params, state, step_in)
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            outs.append(np.asarray(toks))
        jax.block_until_ready(logits)
        dt = time.time() - t0
    n = max_new - 1
    print(f"[serve] fixed-batch decode {n} steps: {dt*1e3:.0f}ms "
          f"({B * max(n, 1) / max(dt, 1e-9):.1f} tok/s)")
    print("[serve] sample token ids:",
          np.concatenate(outs, axis=1)[0][:16].tolist())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate (requests per decode step)")
    ap.add_argument("--prompt-len-min", type=int, default=8)
    ap.add_argument("--prompt-len-max", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="pool-global nucleus sampling threshold")
    ap.add_argument("--backend", default="auto", choices=("auto", "xla"))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = cfgbase.get_config(args.arch)
    if args.reduced:
        cfg = cfgbase.reduced(cfg)

    mesh = mesh_from_arg(args.mesh)
    dp_axes = mesh_dp_axes(mesh)

    S = page_len(cfg, args.prompt_len_max, args.max_new)
    scfg = ServeConfig(dp_axes=dp_axes, backend=args.backend)
    fns = make_serve_fns(cfg, scfg, mesh, args.slots, S)
    params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(args.seed))
    if fns.insert is None:
        print(f"[serve] {args.arch}: pool unsupported (recurrent blocks / "
              f"MoE capacity dispatch / frontend) — legacy fixed-batch loop")
        run_fixed_batch(cfg, fns, params, mesh, args.slots,
                        args.prompt_len_max, args.max_new, seed=args.seed)
        return
    if fns.shardings["plan"]:
        print(f"[serve] collective plan ({scfg.topology}):")
        for k, v in sorted(fns.shardings["plan"].items()):
            print(f"[serve]   {k:24s} -> {v}")

    trace = poisson_trace(
        args.requests, args.rate, (args.prompt_len_min, args.prompt_len_max),
        args.max_new, cfg.vocab_size, seed=args.seed,
        temperature=args.temperature)

    with set_mesh(mesh):
        sched = ContinuousBatchingScheduler(
            cfg, fns, params, args.slots, S, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed)
        for req in trace:
            sched.submit(req)
        t0 = time.time()
        stats = sched.run()
        dt = time.time() - t0

    print(f"[serve] {args.requests} requests, {args.slots} pages x {S} tokens,"
          f" backend={args.backend}")
    print(f"[serve] {stats['tokens_out']} tokens in {dt*1e3:.0f}ms "
          f"({stats['tokens_out'] / max(dt, 1e-9):.1f} tok/s), "
          f"{stats['decode_steps']} decode steps, "
          f"occupancy mean {stats['mean_occupancy']:.2f} / "
          f"peak {stats['peak_occupancy']} of {args.slots}")
    print(f"[serve] traces: {fns.trace_counts}")
    done = [r for r in trace if r.finished]
    print(f"[serve] finished {len(done)}/{len(trace)}; sample request 0 ids:",
          trace[0].generated[:16])
    if len(done) != len(trace):
        raise SystemExit(f"[serve] {len(trace) - len(done)} requests did "
                         f"not finish")
    return {"arch": cfg.name, "n_layers": cfg.n_layers, "wall_s": dt,
            "page_len": S, "requests": trace,
            "trace_counts": dict(fns.trace_counts), **stats}


if __name__ == "__main__":
    main()
