"""jit'd public wrapper around the flash-attention Pallas kernel.

Accepts the model's layout ([B, T, nh, hd] Q and [B, T, nkv, hd] K/V),
pads sequence lengths to the tile size, and dispatches to the kernel
(interpret mode on CPU; compiled on TPU).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_kernel


def _pad_to(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@partial(jax.jit, static_argnames=("window", "causal", "bq", "bk",
                                   "interpret"))
def flash_attention(q, k, v, *, window=None, causal: bool = True,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """q: [B, Tq, nh, hd]; k, v: [B, Tk, nkv, hd] -> [B, Tq, nh, hd]."""
    B, Tq, nh, hd = q.shape
    Tk, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    qg = q.reshape(B, Tq, nkv, g, hd).transpose(0, 2, 3, 1, 4)
    kg = k.transpose(0, 2, 1, 3)
    vg = v.transpose(0, 2, 1, 3)
    bq_ = min(bq, max(16, Tq))
    bk_ = min(bk, max(16, Tk))
    qg, pq = _pad_to(qg, 3, bq_)
    kg, _ = _pad_to(kg, 2, bk_)
    vg, _ = _pad_to(vg, 2, bk_)
    out = flash_attention_kernel(qg, kg, vg, window=window, causal=causal,
                                 bq=bq_, bk=bk_, interpret=interpret)
    if pq:
        out = out[:, :, :, :Tq]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, nh, hd)
