"""Operations and bytes that the algorithm needs, from a configuration's
shapes alone: the same whatever implements it.  Multiply-adds count two
operations; recomputation, padding and masked-out work do not count.
"""

from __future__ import annotations

from bench.model import dims


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer that multiply every token: q, k, v, o and the
    three SwiGLU matrices."""
    k = dims(c)
    d, nh, nkv, hd, f = k["d"], k["nh"], k["nkv"], k["hd"], k["f"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f


def matmul_params(c: dict) -> int:
    """Weights that multiply every token, the output head included (the
    embedding lookup is no product)."""
    k = dims(c)
    return k["L"] * layer_matmul_params(c) + k["d"] * k["V"]


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward (3x the forward) per token of a causal
    sequence of ``seq_len``: 6 per matmul weight, and per layer
    ``6 nh hd (T + 1)`` for the scores and the weighted values over the
    ``(T + 1) / 2`` keys a query sees on average."""
    k = dims(c)
    attn = 6 * k["nh"] * k["hd"] * (seq_len + 1)
    return 6.0 * matmul_params(c) + k["L"] * attn


def decode_flops(c: dict, ctx: int, n_active: int) -> float:
    """One decode step of ``n_active`` sequences whose ``ctx`` keys (their
    sum over the sequences) each query attends to."""
    k = dims(c)
    return 2.0 * matmul_params(c) * n_active \
        + k["L"] * 4.0 * k["nh"] * k["hd"] * ctx


def decode_bytes(c: dict, ctx: int, n_active: int, itemsize: int = 2
                 ) -> float:
    """One decode step: every weight read once, and each active
    sequence's cached keys and values up to its own position."""
    k = dims(c)
    norms = k["L"] * 2 * k["d"] + k["d"] \
        + (2 * k["L"] * k["hd"] if k["qk_norm"] else 0)
    weights = (matmul_params(c) + norms) * itemsize
    kv = ctx * k["L"] * 2 * k["nkv"] * k["hd"] * itemsize
    return float(weights + kv)
