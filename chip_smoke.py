#!/usr/bin/env python3
"""Smoke run of the main paths on a TPU: proof that the system starts on
the chip and computes the right thing.  A smoke run, not a benchmark: the
times it prints are single cold runs.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # the p=4 collectives and ZeRO steps

One chip:
  * kernels — each fused collective step kernel, compiled, against its
    pure-jnp oracle (``kernels/collectives/ref.py``), bit for bit;
  * serve — ``repro.launch.serve`` serves phi4-mini-3.8b at all 32 layers
    and published widths (bf16 random weights from the seed) through the
    paged-KV pool; every request must finish, and request 0's greedy
    tokens are checked against the plain full-sequence forward pass;
  * train — ``repro.launch.train`` takes steps of phi4-mini-3.8b at
    published widths cut to one layer; the losses must be finite and
    fall, and step 0's loss must equal ``transformer.loss_fn`` on the
    same batch and initial weights.

Four chips (``--four-chips``, and nothing else): allreduce,
reduce_scatter and allgather for every backend and wire against
``lax.psum`` / ``psum_scatter`` / ``all_gather`` over payloads from 4 KiB
to 64 MiB, then a few data-parallel ZeRO steps of phi4-mini-3.8b at
published widths, cut to one layer, per backend against
``--backend xla``: losses step by step, and every parameter leaf's
update.

Every phase raises on failure, and the script exits non-zero.  The last
line of a passing run is one JSON object naming the device.  It refuses
to run anywhere but on a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "phi4-mini-3.8b"


def device_info(want: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); it never runs on the CPU")
    if len(devs) != want:
        raise SystemExit(f"chip_smoke: needs {want} TPU devices, found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def phase_kernels() -> None:
    """Each fused step kernel, compiled, vs its oracle at bucket widths."""
    import jax.numpy as jnp
    import numpy as np

    from repro.collectives import compression as comp
    from repro.kernels.collectives import kernel as K
    from repro.kernels.collectives import ref as R

    rng = np.random.RandomState(0)
    h = 1 << 16
    n = 0

    def same(a, b, what):
        nonlocal n
        for x, y in zip(a if isinstance(a, tuple) else (a,),
                        b if isinstance(b, tuple) else (b,)):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                raise AssertionError(f"kernel {what} differs from its oracle")
        n += 1

    for dt in (jnp.float32, jnp.bfloat16):
        buf = jnp.asarray(rng.randn(2 * h), dt)
        recv = jnp.asarray(rng.randn(h), dt)
        for c in (0, 1):
            same(K.rs_step_kernel(buf, recv, c), R.rs_step_ref(buf, recv, c),
                 f"rs_step {dt.__name__} c={c}")
            same(K.rs_step_kernel(buf, recv, c, 1 - c),
                 R.rs_step_ref(buf, recv, c, 1 - c),
                 f"rs_step+send {dt.__name__} c={c}")
        v = jnp.asarray(rng.randn(4 * h), dt)
        for ridx in (0, 3):
            same(K.ring_update_kernel(v, recv, ridx, return_updated=True),
                 (R.ring_update_ref(v, recv, ridx),
                  R.ring_update_ref(v, recv, ridx)[ridx * h:(ridx + 1) * h]),
                 f"ring_update {dt.__name__}")
    buf = jnp.asarray(rng.randn(2 * h) * 3, jnp.float32)
    rq, rs = comp.quantize_wire(jnp.asarray(rng.randn(h) * 3, jnp.float32))
    for c in (0, 1):
        same(K.rs_step_kernel_q(buf, rq, rs, c),
             R.rs_step_ref_q(buf, rq, rs, c), f"rs_step_q c={c}")
        same(K.rs_step_kernel_q(buf, rq, rs, c, 1 - c),
             R.rs_step_ref_q(buf, rq, rs, c, 1 - c), f"rs_step_q+send c={c}")
    for dt in (jnp.float32, jnp.bfloat16, jnp.int8):
        a = jnp.asarray(rng.randint(-100, 100, h), dt)
        b = jnp.asarray(rng.randint(-100, 100, h), dt)
        for c in (0, 1):
            same(K.ag_step_kernel(a, b, c), R.ag_step_ref(a, b, c),
                 f"ag_step {dt.__name__} c={c}")
    log(f"kernels: {n} compiled kernel calls equal their oracles bit for bit")


def phase_serve() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import base
    from repro.launch import serve
    from repro.models import transformer as T

    seed = 0
    t0 = time.time()
    out = serve.main(["--arch", ARCH, "--slots", "4", "--requests", "8",
                      "--rate", "1.0", "--prompt-len-min", "128",
                      "--prompt-len-max", "512", "--max-new", "32",
                      "--seed", str(seed)])
    wall = time.time() - t0
    cfg = base.get_config(ARCH)
    if out["n_layers"] != cfg.n_layers:
        raise AssertionError(f"served {out['n_layers']} layers")
    reqs = out["requests"]
    for r in reqs:
        if not (r.finished and r.generated and all(
                0 <= t < cfg.vocab_size for t in r.generated)):
            raise AssertionError(f"request {r.rid}: bad output {r.generated}")
    if any(v > 1 for k, v in out.get("trace_counts", {}).items()):
        raise AssertionError(f"pool retraced: {out['trace_counts']}")

    # request 0 against the plain full-sequence forward pass: teacher-force
    # prompt + generated tokens and compare each pool token's reference
    # logit with the reference maximum (logits, not tokens: with random
    # weights a near-tie may flip under rounding)
    r = reqs[0]
    gc.collect()    # the launcher's weights must be gone before these
    params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(seed))
    seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
    S = out["page_len"]
    padded = np.zeros((1, S), np.int32)
    padded[0, :len(seq)] = seq
    L = len(r.prompt)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, x: T.forward(p, cfg, x)[0])(
            params, jnp.asarray(padded))
    ref = np.asarray(logits[0, L - 1:len(seq)], np.float32)
    gen = np.asarray(r.generated)
    chosen = ref[np.arange(len(gen)), gen]
    top = ref.max(-1)
    spread = ref.std(-1)
    gap = (top - chosen) / spread
    agree = float((ref.argmax(-1) == gen).mean())
    log(f"serve: request 0 vs forward: argmax agreement {agree:.3f}, "
        f"worst logit gap {gap.max():.4f} std over {len(gen)} tokens")
    # a pool token must be the reference argmax up to bf16 rounding of
    # 32 layers: within a tenth of a logit std of the top (a broken cache
    # or mask picks tokens some std below it); argmax agreement is only
    # reported, since top-2 gaps over 200k logits are ~0.2 std and a
    # near-tie may flip
    if not np.all(np.isfinite(ref)) or gap.max() > 0.1:
        raise AssertionError("served tokens disagree with the forward pass")
    log(f"serve: {len(reqs)} requests, {out['tokens_out']} tokens, "
        f"{out['decode_steps']} decode steps, pages of {S} tokens; "
        f"wall {wall:.1f} s incl. compile, serve loop {out['wall_s']:.2f} s "
        f"({out['tokens_out'] / out['wall_s']:.1f} tok/s, smoke run, cold)")


def _loss_at_init(cfg, batch_size, seq, seed):
    """``transformer.loss_fn`` on the train launcher's step-0 batch and
    initial weights: the reference for step 0's reported loss."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    from repro.train.data import DataConfig, make_batch

    dcfg = DataConfig(global_batch=batch_size, seq_len=seq,
                      vocab_size=cfg.vocab_size, seed=seed + 1)
    b = {k: jnp.asarray(v) for k, v in make_batch(dcfg, 0).items()}
    gc.collect()    # the launcher's weights and state must be gone
    params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(seed))
    return float(jax.jit(lambda p, b: T.loss_fn(p, cfg, b)[0])(params, b))


def run_train(backend: str, batch: int, seq: int, steps: int, mesh: str = "",
              seed: int = 0, keep_params: bool = False) -> dict:
    from repro.launch import train
    argv = ["--arch", ARCH, "--layers", "1", "--steps", str(steps),
            "--batch", str(batch), "--seq", str(seq), "--backend", backend,
            "--warmup", "2", "--lr", "1e-3", "--log-every", "1",
            "--seed", str(seed)]
    if mesh:
        argv += ["--mesh", mesh]
    out = train.main(argv)
    if not keep_params:
        del out["params"]
    losses = out["losses"]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"train {backend}: losses {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train {backend}: loss did not fall {losses}")
    return out


def phase_train() -> None:
    from repro.configs import base

    batch, seq, steps = 4, 512, 8      # 12.5 GB by compiled memory analysis
    t0 = time.time()
    out = run_train("bine", batch, seq, steps)
    wall = time.time() - t0
    cfg = base.get_config(ARCH).replace(n_layers=1)
    ref = _loss_at_init(cfg, batch, seq, seed=0)
    got = out["losses"][0]
    if abs(got - ref) > 1e-3 * abs(ref):
        raise AssertionError(f"train step-0 loss {got} != loss_fn {ref}")
    steady = sorted(out["step_s"][2:])[len(out["step_s"][2:]) // 2]
    log(f"train: {out['n_params']:,} params (1 of 32 layers, published "
        f"widths), losses {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f}, "
        f"step-0 loss equals loss_fn ({ref:.4f}); first step "
        f"{out['step_s'][0]:.1f} s incl. compile, median step "
        f"{steady * 1e3:.0f} ms, {out['tokens_per_step'] / steady:.0f} "
        f"tokens/s (smoke run, not a benchmark); wall {wall:.1f} s")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

#: full-vector payloads of the collective checks, bytes of float32
PAYLOADS = (4 << 10, 1 << 20, 64 << 20)
BACKENDS = ("bine", "recdoub", "ring", "bine_hier", "pallas_fused", "auto")
#: what a compiled Pallas kernel leaves in the TPU executable's HLO
KERNEL_MARK = "tpu_custom_call"
CODEC = ("bine", "recdoub", "pallas_fused", "auto")


def phase_collectives4() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.collectives import api
    from repro.kernels.collectives import ops as fused_ops
    from repro.launch.mesh import make_mesh

    p = 4
    mesh = make_mesh((p,), ("x",))
    rng = np.random.RandomState(0)
    refs = {
        "allreduce": lambda v: lax.psum(v, "x"),
        "reduce_scatter": lambda v: lax.psum_scatter(v, "x", tiled=True),
        "allgather": lambda v: lax.all_gather(v, "x", tiled=True),
    }
    calls = {
        "allreduce": api.allreduce, "reduce_scatter": api.reduce_scatter,
        "allgather": api.allgather,
    }

    def run(fn, x):
        f = jax.jit(jax.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                                  in_specs=P("x"), out_specs=P("x")))
        compiled = f.lower(x).compile()
        out = compiled(x)
        devs = {s.device for s in out.addressable_shards}
        if len(devs) != p:
            raise AssertionError(f"output on {len(devs)} devices, not {p}")
        return np.asarray(out), compiled.as_text()

    n_ok = fallbacks = 0
    for nbytes in PAYLOADS:
        n = nbytes // 4
        full = (rng.randn(p, n) * 3).astype(np.float32)
        for coll in ("allreduce", "reduce_scatter", "allgather"):
            x = full if coll != "allgather" else full[:, :n // p]
            x = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("x")))
            want, _ = run(refs[coll], x)
            for backend in BACKENDS:
                wires = ("float32",)
                if coll != "allreduce" and backend in CODEC:
                    wires += ("int8",)
                for wire in wires:
                    cfg = api.CollectiveConfig(backend=backend,
                                               wire_dtype=wire)
                    got, hlo = run(lambda v: calls[coll](v, "x", cfg), x)
                    scale = np.abs(np.asarray(x)).sum(0).max()
                    tol = (4.0 * scale / 127.0 * math.log2(p)
                           if wire == "int8" else 1e-5 * scale)
                    err = float(np.abs(got - want).max())
                    if err > tol:
                        raise AssertionError(
                            f"{coll} {backend}/{wire} {nbytes} B: max err "
                            f"{err} > {tol}")
                    if backend == "pallas_fused" and not (
                            coll == "allreduce"
                            and nbytes <= cfg.small_cutoff_bytes):
                        # every rank block is n // p elements
                        fb = fused_ops.shmap_fallback(
                            n // p, np.float32,
                            "int8" if wire == "int8" else "plain")
                        if fb:
                            fallbacks += 1
                            log(f"{coll} pallas_fused/{wire} {nbytes} B: "
                                f"rank block of {n // p} elements is not "
                                f"whole kernel tiles; the shmap path ran")
                        if (KERNEL_MARK in hlo) == fb:
                            raise AssertionError(
                                f"{coll} pallas_fused/{wire} {nbytes} B: "
                                f"tpu_custom_call {'in' if fb else 'not in'}"
                                f" the compiled HLO")
                    n_ok += 1
    log(f"collectives p=4: {n_ok} (collective, backend, wire, payload) "
        f"cells agree with lax; {fallbacks} pallas_fused cells ran the "
        f"shmap path")


def _leaf_update_gaps(params, ref, init):
    """Per leaf: sum |params - ref| / sum |ref - init|, the share of the
    reference's update that a backend's update misses or adds."""
    import jax
    import numpy as np
    gaps = []
    for a, r, i in zip(jax.tree.leaves(params), ref, init):
        a = np.asarray(a, np.float32)
        moved = np.abs(r - i).sum()
        gaps.append(float(np.abs(a - r).sum() / max(moved, 1e-30)))
    return gaps


def phase_train4() -> None:
    """ZeRO steps of phi4-mini at published widths, one layer, data
    parallel over the four chips, per backend against xla."""
    import jax
    import numpy as np

    from repro.configs import base
    from repro.models import transformer as T

    steps, batch, seq = 6, 8, 256
    base_out = run_train("xla", batch, seq, steps, mesh="4,1",
                         keep_params=True)
    ref = [np.asarray(x, np.float32)
           for x in jax.tree.leaves(base_out.pop("params"))]
    cfg = base.get_config(ARCH).replace(n_layers=1)
    init = [np.asarray(x, np.float32) for x in jax.tree.leaves(
        jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(0)))]
    for backend in ("bine", "pallas_fused", "auto"):
        out = run_train(backend, batch, seq, steps, mesh="4,1",
                        keep_params=True)
        diff = max(abs(a - b) for a, b in zip(out["losses"],
                                               base_out["losses"]))
        g0, g0_ref = out["grad_norms"][0], base_out["grad_norms"][0]
        gap = max(_leaf_update_gaps(out.pop("params"), ref, init))
        log(f"train p=4 {backend}: {out['n_params']:,} params, losses "
            f"{out['losses']} vs xla {base_out['losses']} (max diff "
            f"{diff:.2e}); step-0 grad norm {g0!r} vs xla {g0_ref!r}; "
            f"worst leaf update gap {gap:.2e}")
        # The step-0 gradient norm is read after the reduce-scatter, from
        # the same weights and batch: a dropped or mis-scaled rank moves
        # it by far more than f32 reduction order (~1e-7), while Adam's
        # sign-like first steps hide a scale error from the loss.  The
        # update gap catches blocks that land on the wrong rank.  Early
        # full-width steps amplify reduction-order noise: on four v5e chips,
        # bine vs xla moved the loss by 1.85e-4 and the worst leaf's
        # update by 0.6% over six steps.
        if abs(g0 - g0_ref) > 1e-5 * g0_ref or diff > 1e-3 or gap > 5e-2:
            raise AssertionError(f"train p=4 {backend} disagrees with xla")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the p=4 collectives and ZeRO steps")
    args = ap.parse_args(argv)

    want = 4 if args.four_chips else 1
    device = device_info(want)
    from repro.launch.mesh import enable_compile_cache
    log(f"device {device}; compile cache {enable_compile_cache()}")
    phases = ([phase_collectives4, phase_train4] if args.four_chips
              else [phase_kernels, phase_serve, phase_train])
    for phase in phases:
        t0 = time.time()
        phase()
        log(f"{phase.__name__} passed in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
