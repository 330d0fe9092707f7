"""Fused RMSNorm kernel vs oracle across shapes/dtypes."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref


@pytest.mark.parametrize("shape", [(8, 64), (256, 128), (3, 7, 96),
                                   (1000, 48)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_rmsnorm(shape, dtype, tol):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape), dtype)
    w = jnp.asarray(rng.randn(shape[-1]) * 0.1, dtype)
    out = rmsnorm(x, w, interpret=True)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
