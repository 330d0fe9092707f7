"""Pallas TPU flash-attention kernel: causal GQA with optional sliding
window, online-softmax accumulation across KV blocks.

TPU adaptation of the GPU flash algorithm (DESIGN.md hardware notes):
  * grid = (B·n_kv, n_q_blocks, n_kv_blocks): the KV axis is innermost so
    the sequential TPU grid revisits the same output block while the
    (m, l, acc) running statistics live in VMEM scratch — the TPU
    equivalent of a warp-persistent accumulator;
  * BlockSpecs tile Q [g·bq, hd] and K/V [bk, hd] into VMEM with
    MXU-aligned tiles (bq = bk = 128 by default; hd is the lane dim);
  * causal + window skipping at *block* granularity via pl.when (dead
    tiles cost zero MXU work), element masks only on edge blocks;
  * GQA folds g = n_q_heads / n_kv_heads into the Q-tile rows, so one
    (g·bq, hd)x(hd, bk) MXU matmul serves the whole KV-head group.

Validated in interpret mode against ref.py over shape/dtype sweeps
(tests/kernels/test_flash_attention.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 bq: int, bk: int, g: int, seq_k: int, window, scale: float,
                 causal: bool):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(live)
    def _compute():
        hd = q_ref.shape[-1]
        q = q_ref[0].reshape(g * bq, hd)
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [g·bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (g * bq, bk), 0) % bq
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 1)
        mask = kpos < seq_k
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        hd = o_ref.shape[-1]
        l = jnp.maximum(l_ref[...], 1e-30)
        out = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        o_ref[0] = out.reshape(g, bq, hd)


def flash_attention_kernel(q, k, v, *, window=None, causal: bool = True,
                           bq: int = 128, bk: int = 128, scale=None,
                           interpret: bool = False):
    """q: [B, nkv, g, Tq, hd]; k, v: [B, nkv, Tk, hd] -> like q.

    Tq % bq == 0 and Tk % bk == 0 (ops.py pads and unpads).
    """
    B, nkv, g, Tq, hd = q.shape
    Tk = k.shape[2]
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    assert Tq % bq == 0 and Tk % bk == 0, (Tq, bq, Tk, bk)
    nq, nk = Tq // bq, Tk // bk
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(B * nkv, g, Tq, hd)
    kr = k.reshape(B * nkv, Tk, hd)
    vr = v.reshape(B * nkv, Tk, hd)

    kernel = functools.partial(
        _attn_kernel, bq=bq, bk=bk, g=g, seq_k=Tk, window=window,
        scale=scale, causal=causal)

    out = pl.pallas_call(
        kernel,
        grid=(B * nkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, g, bq, hd), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, bq, hd), lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * nkv, g, Tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq,), jnp.float32),      # running max m
            pltpu.VMEM((g * bq,), jnp.float32),      # running denom l
            pltpu.VMEM((g * bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, nkv, g, Tq, hd)
