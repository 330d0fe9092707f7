"""Fused int8-dequantize-accumulate Pallas kernel.

The hot loop of a *compressed* gradient reduce-scatter: at every butterfly
step the received int8 payload must be dequantized (per-chunk scales) and
added to the local fp32 partial.  Fusing dequant+add keeps the int8 wire
format all the way into the accumulator — one VMEM pass instead of
materializing the dequantized fp32 tensor in HBM first (3x traffic cut on
the accumulate: read q(1B)+scale+acc(4B), write acc(4B), vs +8B for a
separate dequant).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _qacc_kernel(q_ref, s_ref, a_ref, o_ref, *, chunk: int):
    q = q_ref[...].astype(jnp.float32)          # [bn, chunk]
    s = s_ref[...].astype(jnp.float32)          # [bn, 1]
    o_ref[...] = a_ref[...] + q * s


def qacc_kernel(q, scales, acc, *, block_chunks: int = 64,
                interpret: bool = False):
    """q: [C, chunk] int8; scales: [C, 1] f32; acc: [C, chunk] f32."""
    C, chunk = q.shape
    bn = min(block_chunks, C)
    assert C % bn == 0
    return pl.pallas_call(
        functools.partial(_qacc_kernel, chunk=chunk),
        grid=(C // bn,),
        in_specs=[
            pl.BlockSpec((bn, chunk), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, chunk), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((C, chunk), jnp.float32),
        interpret=interpret,
    )(q, scales, acc)
