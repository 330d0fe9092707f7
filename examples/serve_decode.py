"""Continuous-batching serving example: a small Poisson trace of
mixed-length prompts streams through the paged-KV scheduler on 8
simulated devices — requests prefill into free pages as they arrive,
decode interleaved, and retire on their token budget, recycling pages.

  PYTHONPATH=src python examples/serve_decode.py --arch gemma3-4b
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from jax import set_mesh  # noqa: E402
from repro.configs import base  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serve.engine import (ServeConfig, make_serve_fns,  # noqa: E402
                                page_len)
from repro.serve.scheduler import (ContinuousBatchingScheduler,  # noqa: E402
                                   poisson_trace)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.5)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prompt-max", type=int, default=40)
    args = ap.parse_args()

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = base.reduced(base.get_config(args.arch))
    S = page_len(cfg, args.prompt_max, args.max_new)
    fns = make_serve_fns(cfg, ServeConfig(dp_axes=("data",)), mesh,
                         args.slots, S)
    params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(0))
    if fns.insert is None:
        # recurrent / MoE / frontend archs: legacy lock-step loop
        from repro.launch.serve import run_fixed_batch
        print(f"{cfg.name}: pool unsupported — legacy fixed-batch loop")
        run_fixed_batch(cfg, fns, params, mesh, args.slots, args.prompt_max,
                        args.max_new)
        return
    trace = poisson_trace(args.requests, args.rate, (4, args.prompt_max),
                          args.max_new, cfg.vocab_size, seed=0,
                          temperature=args.temperature)

    with set_mesh(mesh):
        sched = ContinuousBatchingScheduler(cfg, fns, params, args.slots, S)
        for req in trace:
            sched.submit(req)
        t0 = time.time()
        stats = sched.run()
        dt = time.time() - t0

    print(f"{stats['tokens_out']} tokens / {stats['decode_steps']} decode "
          f"steps in {dt*1e3:.0f} ms "
          f"({stats['tokens_out'] / max(dt, 1e-9):.1f} tok/s)")
    print(f"occupancy mean {stats['mean_occupancy']:.2f} "
          f"peak {stats['peak_occupancy']} of {args.slots}; "
          f"traces: {fns.trace_counts}")
    for req in trace[:4]:
        print(f"req {req.rid}: prompt {len(req.prompt):2d} toks, "
              f"arrived {req.arrival:5.1f}, finished {req.finished_at:5.1f} "
              f"({req.finish_reason}): {req.generated[:10]}")


if __name__ == "__main__":
    main()
