"""End-to-end driver: train the ~100M-parameter xlstm-125m architecture
(FULL assigned config, not reduced) for a few hundred steps.

  PYTHONPATH=src python examples/train_lm_100m.py --steps 300

On the CPU container a step takes seconds; pass --steps 25 for a quick
demonstration (loss visibly decreases by step ~20).  The same driver
scales to the production meshes (see repro.launch.train).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import base  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train import checkpoint as ckpt  # noqa: E402
from repro.train.data import DataConfig, Prefetcher  # noqa: E402
from jax import set_mesh  # noqa: E402
from repro.train.step import (TrainConfig, make_init_fns,  # noqa: E402
                              make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_100m_ckpt")
    args = ap.parse_args()

    mesh = make_mesh((8, 1), ("data", "model"))
    cfg = base.get_config("xlstm-125m")          # FULL assigned config
    tcfg = TrainConfig(
        backend="bine", dp_axes=("data",),
        adamw=AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps))
    key = jax.random.key(0)
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    print(f"training {cfg.name}: {n/1e6:.1f}M params, "
          f"batch {args.batch} x seq {args.seq}, {args.steps} steps")

    step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, shapes)
    init_p, init_s = make_init_fns(cfg, tcfg, mesh, shapes)
    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq,
                      vocab_size=cfg.vocab_size)
    cpr = ckpt.AsyncCheckpointer(args.ckpt_dir)

    with set_mesh(mesh):
        params = init_p(key)
        state = init_s(params)
        pf = Prefetcher(dcfg)
        try:
            t0 = time.time()
            for s in range(args.steps):
                _, b = pf.next()
                batch = {k: jax.device_put(v, shardings["batch"][k])
                         for k, v in b.items()}
                params, state, m = step_fn(params, state, batch)
                if s % 10 == 0 or s == args.steps - 1:
                    print(f"step {s:4d}  loss {float(m['loss']):.4f}  "
                          f"gnorm {float(m['grad_norm']):.2f}  "
                          f"{(time.time()-t0)/(s+1):.2f}s/step")
                if (s + 1) % 100 == 0:
                    cpr.save(s + 1, {"params": params, "state": state})
            cpr.save(args.steps, {"params": params, "state": state},
                     block=True)
        finally:
            pf.close()
    print("done; checkpoints in", args.ckpt_dir)


if __name__ == "__main__":
    main()
