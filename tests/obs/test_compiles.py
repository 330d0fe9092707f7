"""The compile counter: a ``jax.compile`` instant on the host clock for
each program compiled (or loaded from the persistent cache), none for a
call that hits the in-memory cache."""

import time

import jax
import jax.numpy as jnp

from repro.obs import compiles, metrics


def test_new_shape_compiles_cached_call_does_not(fresh_timeline):
    @jax.jit
    def counted_fn(x):
        return jnp.tanh(x) * 3.0 + 1.0

    t0 = time.perf_counter()
    counted_fn(jnp.ones(3)).block_until_ready()
    t1 = time.perf_counter()
    counted_fn(jnp.ones(3)).block_until_ready()      # in-memory cache
    t2 = time.perf_counter()
    counted_fn(jnp.ones(5)).block_until_ready()      # a new shape
    t3 = time.perf_counter()

    def mine(lo, hi):
        return [e for e in compiles.programs(lo, hi)
                if "counted_fn" in str(e.args.get("fun_name", ""))]

    first, cached, reshaped = mine(t0, t1), mine(t1, t2), mine(t2, t3)
    assert len(first) == 1 and cached == [] and len(reshaped) == 1
    e = first[0]
    assert e.lane == "runtime" and e.dur_us is None
    assert 0 < e.args["duration_s"] <= t1 - t0
    # a cache load, if the persistent cache served one, lies within its
    # compile span and is not counted again
    loads = [x for x in fresh_timeline.events if x.name == "jax.cache_load"]
    assert len(loads) <= 2


def test_counter_is_off_with_the_instrumentation(fresh_timeline):
    with metrics.disabled():
        jax.jit(lambda x: x * 7.0 - 2.0)(jnp.ones(4)).block_until_ready()
    assert len(fresh_timeline) == 0
