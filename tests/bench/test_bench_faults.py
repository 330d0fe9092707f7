"""A run whose timed path is broken underneath comes out not correct:
one fault a cell can have at a time, at tiny sizes on the CPU, through
the rest of a run (the look for a chip skipped)."""

import jax.numpy as jnp

import bench_helpers as bc


def test_state_returned_unchanged(monkeypatch):
    from repro.train import step
    monkeypatch.setattr(step, "adamw_update_leaf",
                        lambda cfg, st, g, s, lr: (st["master"], st))
    run = bc.run_cell(bc.tiny_run("train1.qwen3-32b.stage"))
    assert not run.correct, run.checks


def test_half_the_batch_left_out(monkeypatch):
    from repro.models import transformer as T
    full = T.loss_fn

    def half(params, cfg, batch):
        return full(params, cfg, {k: v[:v.shape[0] // 2]
                                  for k, v in batch.items()})
    monkeypatch.setattr(T, "loss_fn", half)
    run = bc.run_cell(bc.tiny_run("train1.qwen3-32b.stage", batch=2))
    assert not run.correct, run.checks


def test_token_altered_where_produced(monkeypatch):
    from repro.serve import sampling
    make = sampling.make_sampler

    def altered(*a, **kw):
        f = make(*a, **kw)

        def sample(logits, temperature, rids, steps, key):
            tok = f(logits, temperature, rids, steps, key)
            return jnp.where(steps == 1, (tok + 1) % logits.shape[-1], tok)
        return sample
    monkeypatch.setattr(sampling, "make_sampler", altered)
    run = bc.run_cell(bc.tiny_run("serve.phi4-mini.chat", seconds=2.0))
    assert not run.correct, run.checks
