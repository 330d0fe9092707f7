"""Pallas flash attention: shape/dtype sweep vs the pure-jnp oracle
(interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref

CASES = [
    # (B, T, nh, nkv, hd, window, dtype, tol)
    (2, 256, 4, 2, 64, None, jnp.float32, 2e-5),
    (1, 384, 8, 2, 128, None, jnp.float32, 2e-5),
    (2, 256, 4, 4, 64, 64, jnp.float32, 2e-5),
    (1, 128, 4, 1, 32, None, jnp.bfloat16, 3e-2),
    (1, 256, 8, 8, 64, 32, jnp.bfloat16, 3e-2),
    (1, 130, 2, 2, 64, 48, jnp.float32, 2e-5),     # padding path
    (1, 257, 2, 1, 16, None, jnp.float32, 2e-5),   # padding, MQA, tiny hd
]


def _ref(q, k, v, window):
    B, T, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(B, T, nkv, g, hd).transpose(0, 2, 3, 1, 4)
    out = flash_attention_ref(qg, k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), window=window)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, nh, hd)


@pytest.mark.parametrize("B,T,nh,nkv,hd,window,dtype,tol", CASES)
def test_flash_vs_ref(B, T, nh, nkv, hd, window, dtype, tol):
    rng = np.random.RandomState(hash((B, T, nh)) % 2**31)
    q = jnp.asarray(rng.randn(B, T, nh, hd), dtype)
    k = jnp.asarray(rng.randn(B, T, nkv, hd), dtype)
    v = jnp.asarray(rng.randn(B, T, nkv, hd), dtype)
    out = flash_attention(q, k, v, window=window, bq=128, bk=128,
                          interpret=True)
    ref = _ref(q, k, v, window)
    err = np.max(np.abs(np.asarray(out, np.float32)
                        - np.asarray(ref, np.float32)))
    assert err < tol, err


def test_block_size_sweep():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 256, 4, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
    ref = _ref(q, k, v, None)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        out = flash_attention(q, k, v, bq=bq, bk=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
