"""jit'd wrapper for the dequantize-accumulate kernel."""

from __future__ import annotations

from functools import partial

import jax

from .kernel import qacc_kernel


@partial(jax.jit, static_argnames=("interpret",))
def dequant_accumulate(q, scales, acc, interpret: bool = False):
    """q: [C, chunk] int8; scales: [C, 1] f32; acc: [C, chunk] f32."""
    C = q.shape[0]
    bn = 64
    while C % bn and bn > 1:
        bn //= 2
    return qacc_kernel(q, scales, acc, block_chunks=bn, interpret=interpret)
