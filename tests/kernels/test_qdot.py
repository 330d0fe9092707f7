"""Dequantize-accumulate kernel vs oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.qdot import dequant_accumulate, dequant_accumulate_ref


@pytest.mark.parametrize("C,chunk", [(64, 128), (100, 256), (1, 64)])
def test_qacc(C, chunk):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randint(-127, 128, size=(C, chunk)), jnp.int8)
    s = jnp.asarray(np.abs(rng.randn(C, 1)) * 0.01, jnp.float32)
    acc = jnp.asarray(rng.randn(C, chunk), jnp.float32)
    out = dequant_accumulate(q, s, acc, interpret=True)
    ref = dequant_accumulate_ref(q, s, acc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
