"""jit'd wrapper for the fused RMSNorm kernel (handles any leading dims)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import rmsnorm_kernel


@partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x, w, eps: float = 1e-6, interpret: bool = False):
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    N = xf.shape[0]
    # pick a row block that divides N
    bn = 256
    while N % bn and bn > 1:
        bn //= 2
    out = rmsnorm_kernel(xf, w, eps=eps, block_rows=bn, interpret=interpret)
    return out.reshape(shape)
