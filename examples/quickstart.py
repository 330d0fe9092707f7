"""Quickstart: train a tiny LM with Bine gradient collectives on the
devices you have (works on a single CPU).

  PYTHONPATH=src python examples/quickstart.py
"""

import os

# use 8 virtual host devices so the collectives actually communicate
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import base  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train.data import DataConfig, make_batch  # noqa: E402
from jax import set_mesh  # noqa: E402
from repro.train.step import (TrainConfig, make_init_fns,  # noqa: E402
                              make_train_step)


def main():
    # 2 "pods" x 2-way data parallel x 2-way model parallel
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = base.reduced(base.get_config("phi4-mini-3.8b"))
    tcfg = TrainConfig(
        backend="bine",                      # the paper's collectives
        dp_axes=("pod", "data"),
        adamw=AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=40),
    )
    key = jax.random.key(0)
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
    step_fn, shardings, _ = make_train_step(cfg, tcfg, mesh, shapes)
    init_p, init_s = make_init_fns(cfg, tcfg, mesh, shapes)
    dcfg = DataConfig(global_batch=8, seq_len=64, vocab_size=cfg.vocab_size)

    with set_mesh(mesh):
        params = init_p(key)
        state = init_s(params)
        print(f"arch={cfg.name} (reduced) params="
              f"{sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)):,}")
        for s in range(40):
            b = make_batch(dcfg, s)
            batch = {k: jax.device_put(v, shardings["batch"][k])
                     for k, v in b.items()}
            params, state, m = step_fn(params, state, batch)
            if s % 5 == 0 or s == 39:
                print(f"step {s:3d}  loss {float(m['loss']):.4f}  "
                      f"lr {float(m['lr']):.2e}")
    print("quickstart done — gradient sync ran on Bine reduce-scatter + "
          "allgather schedules (ZeRO-1 sharded optimizer).")


if __name__ == "__main__":
    main()
