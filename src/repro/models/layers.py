"""Core layers: RMSNorm, RoPE, GQA attention (chunked flash-style), MLPs.

Everything is a pure function over param pytrees (dict leaves); no flax.
Attention is computed with a query-chunked, KV-sliced scan so that 32k/500k
sequence cells lower with bounded live memory, mirroring the Pallas flash
kernel's tiling (kernels/flash_attention is the TPU runtime path; this is
the jnp oracle used everywhere else).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def rmsnorm(x, w, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def init_rmsnorm(d: int, dtype) -> jax.Array:
    return jnp.zeros((d,), dtype)


def rope(x, positions, theta: float):
    """x: [..., T, H, hd]; positions: [..., T]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = (theta ** (-np.arange(0, half) / half)).astype(np.float32)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., T, half]
    cos = jnp.cos(ang)[..., :, None, :]  # [..., T, 1, half]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1)
    return out.astype(x.dtype)


def dense(x, w):
    return jnp.einsum("...d,df->...f", x, w)


def init_dense(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> dict:
    d, nh, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": init_dense(ks[0], d, nh * hd, dt),
        "wk": init_dense(ks[1], d, nkv * hd, dt),
        "wv": init_dense(ks[2], d, nkv * hd, dt),
        "wo": init_dense(ks[3], nh * hd, d, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dt)
        p["k_norm"] = init_rmsnorm(hd, dt)
    return p


def _qkv(p, cfg, x, positions):
    B, T, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(x, p["wq"]).reshape(B, T, nh, hd)
    k = dense(x, p["wk"]).reshape(B, T, nkv, hd)
    v = dense(x, p["wv"]).reshape(B, T, nkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa_chunk(q, k, v, qpos, kpos, window, scale):
    """One (query-chunk × kv-slice) attention tile; f32 accumulation.

    q: [B,Tq,nh,hd]  k/v: [B,Tk,nkv,hd].  Returns (out, row_max, row_sum)
    partial-softmax triple for combination across kv slices.
    """
    B, Tq, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(B, Tq, nkv, g, hd)
    s = jnp.einsum("btkgh,bskh->bktgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale  # [B,nkv,Tq,g,Tk]
    mask = (kpos[None, :] <= qpos[:, None])
    if window is not None:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[None, None, :, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                       # [B,nkv,Tq,g]
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(s - m_safe[..., None])
    e = jnp.where(jnp.isfinite(s), e, 0.0)
    denom = jnp.sum(e, axis=-1)                   # [B,nkv,Tq,g]
    o = jnp.einsum("bktgs,bskh->bktgh", e, v.astype(jnp.float32))
    return o, m_safe, denom


@jax.named_scope("attn")
def attention(p, cfg, x, positions, window=None):
    """Causal (optionally windowed) GQA over full sequences.

    Three execution strategies (models.sharding.strategy):
      * ``megatron_sp`` — K/V repeated to n_heads, the tile scan
        head-sharded over the model axis (exact-causal triangular tiles);
      * ``pure_sp``     — the query-chunk grid sharded over the model
        axis, vectorized over chunks (tokens sequence-parallel end to end);
      * ``single``      — query-chunked scan with static KV slices (the
        jnp oracle; CPU tests).
    """
    from . import sharding as sh

    B, T, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(p, cfg, x, positions)
    scale = 1.0 / math.sqrt(hd)
    C = min(cfg.attn_chunk, T)
    nC = T // C
    assert T % C == 0, (T, C)

    strat = sh.strategy(cfg)
    if strat == "megatron_sp":
        out = _attn_head_parallel(cfg, q, k, v, positions, window, scale, C)
        return dense(out.reshape(B, T, nh * hd).astype(x.dtype), p["wo"])
    if strat == "pure_sp" and T % sh.model_parallel() == 0:
        # q-chunk grid must shard over model: grow chunks if nC < n_model
        Cq = C if nC % sh.model_parallel() == 0 else T // sh.model_parallel()
        out = _attn_seq_parallel(cfg, q, k, v, positions, window, scale, Cq)
        return dense(out.reshape(B, T, nh * hd).astype(x.dtype), p["wo"])

    if window is not None and window < T:
        # static KV slice of size window rounded up to chunk multiple + C
        W = ((window + C - 1) // C) * C + C
        W = min(W, T)

        def qchunk(carry, i):
            qs = i * C
            qc = lax.dynamic_slice_in_dim(q, qs, C, axis=1)
            qp = lax.dynamic_slice_in_dim(positions, qs, C, axis=0)
            ks_ = jnp.maximum(qs + C - W, 0)
            kc = lax.dynamic_slice_in_dim(k, ks_, W, axis=1)
            vc = lax.dynamic_slice_in_dim(v, ks_, W, axis=1)
            kp = lax.dynamic_slice_in_dim(
                jnp.arange(T, dtype=positions.dtype), ks_, W, axis=0)
            o, m, dn = _sdpa_chunk(qc, kc, vc, qp, kp, window, scale)
            o = o / jnp.maximum(dn[..., None], 1e-30)
            return carry, o

        _, outs = lax.scan(qchunk, None, jnp.arange(nC))
        out = outs.reshape(nC, B, nkv, C, nh // nkv, hd)
        out = jnp.transpose(out, (1, 0, 3, 2, 4, 5)).reshape(B, T, nh, hd)
    else:
        # full causal: scan over the *lower-triangular* (q-chunk, kv-chunk)
        # pair list so HLO FLOPs = T(T+C)/2·... — exact causal work, no
        # masked-out dead tiles (roofline honesty at 32k).
        g = nh // nkv
        pairs_i = np.concatenate([np.full(i + 1, i) for i in range(nC)])
        pairs_j = np.concatenate([np.arange(i + 1) for i in range(nC)])
        arange_c = jnp.arange(C, dtype=positions.dtype)

        def tile(carry, ij):
            i, j = ij
            o_a, m_a, d_a, out = carry
            qs = i * C
            ks_ = j * C
            qc = lax.dynamic_slice_in_dim(q, qs, C, axis=1)
            qp = lax.dynamic_slice_in_dim(positions, qs, C, axis=0)
            kc = lax.dynamic_slice_in_dim(k, ks_, C, axis=1)
            vc = lax.dynamic_slice_in_dim(v, ks_, C, axis=1)
            kp = (ks_ + arange_c)
            o, m, dn = _sdpa_chunk(qc, kc, vc, qp, kp, None, scale)
            first = (j == 0)
            m_a = jnp.where(first, jnp.full_like(m_a, -jnp.inf), m_a)
            d_a = jnp.where(first, jnp.zeros_like(d_a), d_a)
            o_a = jnp.where(first, jnp.zeros_like(o_a), o_a)
            m_new = jnp.maximum(m_a, m)
            r_a = jnp.exp(jnp.maximum(m_a - m_new, -80.0))
            r_b = jnp.exp(jnp.maximum(m - m_new, -80.0))
            o_a = o_a * r_a[..., None] + o * r_b[..., None]
            d_a = d_a * r_a + dn * r_b
            fin = (o_a / jnp.maximum(d_a[..., None], 1e-30))
            # unconditional slot-i write: the last j-step for each i wins
            out = lax.dynamic_update_slice_in_dim(out, fin[None], i, 0)
            return (o_a, m_new, d_a, out), None

        init = (jnp.zeros((B, nkv, C, g, hd), jnp.float32),
                jnp.full((B, nkv, C, g), -jnp.inf, jnp.float32),
                jnp.zeros((B, nkv, C, g), jnp.float32),
                jnp.zeros((nC, B, nkv, C, g, hd), jnp.float32))
        (_, _, _, outs), _ = lax.scan(
            tile, init, (jnp.asarray(pairs_i), jnp.asarray(pairs_j)))
        out = jnp.transpose(outs, (1, 0, 3, 2, 4, 5)).reshape(B, T, nh, hd)

    out = out.astype(x.dtype).reshape(B, T, nh * hd)
    return dense(out, p["wo"])


def _attn_head_parallel(cfg, q, k, v, positions, window, scale, C):
    """megatron_sp attention: repeat K/V to n_heads, shard heads over the
    model axis, scan exact-causal triangular (q,kv) tiles.

    With heads sharded, every tile einsum splits n_model-ways and the
    dynamic T-slices stay on an unsharded dim — GSPMD lowers this without
    re-gathering (the failure mode of the grouped layout when
    n_kv_heads < n_model; see EXPERIMENTS.md §Perf).
    """
    from .sharding import MODEL_AXIS, shard

    B, T, nh, hd = q.shape
    g = nh // k.shape[2]
    kf = jnp.repeat(k, g, axis=2)          # [B,T,nh,hd]
    vf = jnp.repeat(v, g, axis=2)
    q = shard(q, None, None, MODEL_AXIS, None)
    kf = shard(kf, None, None, MODEL_AXIS, None)
    vf = shard(vf, None, None, MODEL_AXIS, None)
    nC = T // C

    def tile(qc, qp, kc, kp, vc):
        s = jnp.einsum("bqnh,bknh->bnqk", qc.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale    # [B,nh,C,Ck]
        mask = kp[None, :] <= qp[:, None]
        if window is not None:
            mask &= (qp[:, None] - kp[None, :]) < window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        e = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
        dn = e.sum(axis=-1)
        o = jnp.einsum("bnqk,bknh->bnqh", e, vc.astype(jnp.float32))
        return o, m_safe, dn                               # [B,nh,C,hd], [B,nh,C]

    if window is not None and window < T:
        W = min(((window + C - 1) // C) * C + C, T)
        kpos_all = jnp.arange(T, dtype=positions.dtype)

        def qchunk(carry, i):
            qs = i * C
            qc = lax.dynamic_slice_in_dim(q, qs, C, axis=1)
            qp = lax.dynamic_slice_in_dim(positions, qs, C, axis=0)
            ks_ = jnp.maximum(qs + C - W, 0)
            kc = lax.dynamic_slice_in_dim(kf, ks_, W, axis=1)
            vc = lax.dynamic_slice_in_dim(vf, ks_, W, axis=1)
            kp = lax.dynamic_slice_in_dim(kpos_all, ks_, W, axis=0)
            o, m, dn = tile(qc, qp, kc, kp, vc)
            return carry, o / jnp.maximum(dn[..., None], 1e-30)

        _, outs = lax.scan(qchunk, None, jnp.arange(nC))
        # outs: [nC,B,nh,C,hd] -> [B,T,nh,hd]
        return jnp.transpose(outs, (1, 0, 3, 2, 4)).reshape(B, T, nh, hd)

    pairs_i = np.concatenate([np.full(i + 1, i) for i in range(nC)])
    pairs_j = np.concatenate([np.arange(i + 1) for i in range(nC)])
    arange_c = jnp.arange(C, dtype=positions.dtype)

    def tilestep(carry, ij):
        i, j = ij
        o_a, m_a, d_a, out = carry
        qs = i * C
        ks_ = j * C
        qc = lax.dynamic_slice_in_dim(q, qs, C, axis=1)
        qp = lax.dynamic_slice_in_dim(positions, qs, C, axis=0)
        kc = lax.dynamic_slice_in_dim(kf, ks_, C, axis=1)
        vc = lax.dynamic_slice_in_dim(vf, ks_, C, axis=1)
        o, m, dn = tile(qc, qp, kc, ks_ + arange_c, vc)
        first = (j == 0)
        m_a = jnp.where(first, jnp.full_like(m_a, -jnp.inf), m_a)
        d_a = jnp.where(first, jnp.zeros_like(d_a), d_a)
        o_a = jnp.where(first, jnp.zeros_like(o_a), o_a)
        m_new = jnp.maximum(m_a, m)
        r_a = jnp.exp(jnp.maximum(m_a - m_new, -80.0))
        r_b = jnp.exp(jnp.maximum(m - m_new, -80.0))
        o_a = o_a * r_a[..., None] + o * r_b[..., None]
        d_a = d_a * r_a + dn * r_b
        fin = o_a / jnp.maximum(d_a[..., None], 1e-30)
        # write the running estimate at slot i EVERY step: for a fixed i
        # later j-steps overwrite it, so the final (diagonal) write wins —
        # avoids a lax.cond that would copy the whole output carry.
        out = lax.dynamic_update_slice_in_dim(out, fin[None], i, 0)
        return (o_a, m_new, d_a, out), None

    init = (jnp.zeros((B, nh, C, hd), jnp.float32),
            jnp.full((B, nh, C), -jnp.inf, jnp.float32),
            jnp.zeros((B, nh, C), jnp.float32),
            jnp.zeros((nC, B, nh, C, hd), jnp.float32))
    (_, _, _, outs), _ = lax.scan(
        tilestep, init, (jnp.asarray(pairs_i), jnp.asarray(pairs_j)))
    return jnp.transpose(outs, (1, 0, 3, 2, 4)).reshape(B, T, nh, hd)


def _attn_seq_parallel(cfg, q, k, v, positions, window, scale, C):
    """pure_sp attention: the query-chunk grid [B, nC, C, ...] is sharded
    over the model axis and processed VECTORIZED over chunks, scanning the
    KV chunks with an online softmax.  Tokens never leave the
    sequence-parallel layout; K/V replicate over model (these archs have
    small d_model).  Block-masked tiles cost full T² MXU work (2x the
    causal minimum) — the documented baseline trade for head counts that
    do not divide the mesh; see EXPERIMENTS.md §Perf for the striped
    variant.
    """
    from .sharding import MODEL_AXIS, shard

    B, T, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    nC = T // C
    q5 = q.reshape(B, nC, C, nh, hd)
    q5 = shard(q5, None, MODEL_AXIS, None, None, None)
    # chunk positions: [nC, C] static
    qpos = positions.reshape(nC, C)

    if window is not None and window + C < T:
        # banded gather: q chunk i sees the static KV band ending at its
        # last position — exact window FLOPs, fully vectorized over chunks
        Wb = min(((window + C - 1) // C) * C + C, T)
        starts = np.clip(np.arange(nC) * C + C - Wb, 0, T - Wb)
        idx = starts[:, None] + np.arange(Wb)[None, :]      # [nC, Wb] static
        kband = jnp.take(k, jnp.asarray(idx), axis=1)       # [B,nC,Wb,nkv,hd]
        vband = jnp.take(v, jnp.asarray(idx), axis=1)
        kp = jnp.asarray(idx, positions.dtype)              # [nC, Wb]
        qg = q5.reshape(B, nC, C, nkv, g, hd)
        s = jnp.einsum("bicngh,bijnh->bincgj", qg.astype(jnp.float32),
                       kband.astype(jnp.float32)) * scale
        mask = (kp[:, None, :] <= qpos[:, :, None]) & \
               (qpos[:, :, None] - kp[:, None, :] < window)  # [nC,C,Wb]
        s = jnp.where(mask[None, :, None, :, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        e = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
        dn = jnp.maximum(e.sum(axis=-1), 1e-30)
        o = jnp.einsum("bincgj,bijnh->bincgh", e, vband.astype(jnp.float32))
        out = o / dn[..., None]
        return jnp.transpose(out, (0, 1, 3, 2, 4, 5)).reshape(B, T, nh, hd)

    nK = T // C
    kc_all = k.reshape(B, nK, C, nkv, hd)
    vc_all = v.reshape(B, nK, C, nkv, hd)
    kpos_all = jnp.arange(T, dtype=positions.dtype).reshape(nK, C)

    def kvstep(carry, inp):
        o_a, m_a, d_a = carry                  # [B,nC,C,nh,*]
        kc, vc, kp = inp                       # [B,C,nkv,hd], [C]
        qg = q5.reshape(B, nC, C, nkv, g, hd)
        s = jnp.einsum("bicngh,bjnh->bincgj", qg.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale  # [B,nC,nkv,C,g,Ck]
        mask = kp[None, None, :] <= qpos[:, :, None]    # [nC,C,Ck]
        if window is not None:
            mask &= (qpos[:, :, None] - kp[None, None, :]) < window
        s = jnp.where(mask[None, :, None, :, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1)                          # [B,nC,nkv,C,g]
        m_new = jnp.maximum(m_a, m)
        e = jnp.where(jnp.isfinite(s),
                      jnp.exp(s - jnp.where(jnp.isfinite(m_new), m_new,
                                            0.0)[..., None]), 0.0)
        dn = e.sum(axis=-1)
        o = jnp.einsum("bincgj,bjnh->bincgh", e, vc.astype(jnp.float32))
        r = jnp.exp(jnp.maximum(m_a - m_new, -80.0))
        r = jnp.where(jnp.isfinite(m_a), r, 0.0)
        o_a = o_a * r[..., None] + o
        d_a = d_a * r + dn
        return (o_a, m_new, d_a), None

    init = (jnp.zeros((B, nC, nkv, C, g, hd), jnp.float32),
            jnp.full((B, nC, nkv, C, g), -jnp.inf, jnp.float32),
            jnp.zeros((B, nC, nkv, C, g), jnp.float32))
    (o_a, m_a, d_a), _ = lax.scan(
        kvstep, init,
        (jnp.moveaxis(kc_all, 1, 0), jnp.moveaxis(vc_all, 1, 0), kpos_all))
    out = o_a / jnp.maximum(d_a[..., None], 1e-30)       # [B,nC,nkv,C,g,hd]
    out = jnp.transpose(out, (0, 1, 3, 2, 4, 5)).reshape(B, T, nh, hd)
    return out


def decode_attention(p, cfg, x, cache_k, cache_v, pos, window=None):
    """Single-token decode: x [B,1,d], cache [B,S,nkv,hd], pos scalar.

    Returns (out [B,1,d], new_k, new_v)."""
    B = x.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    S = cache_k.shape[1]
    positions = jnp.full((1,), pos, dtype=jnp.int32)
    q, k, v = _qkv(p, cfg, x, positions)
    cache_k = lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype),
                                              pos, axis=1)
    cache_v = lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype),
                                              pos, axis=1)
    g = nh // nkv
    qg = q.reshape(B, 1, nkv, g, hd)
    s = jnp.einsum("btkgh,bskh->bkgs", qg.astype(jnp.float32),
                   cache_k.astype(jnp.float32)) / math.sqrt(hd)
    kpos = jnp.arange(S)
    mask = kpos <= pos
    if window is not None:
        mask &= (pos - kpos) < window
    s = jnp.where(mask[None, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", w, cache_v.astype(jnp.float32))
    o = o.reshape(B, 1, nh * hd).astype(x.dtype)
    return dense(o, p["wo"]), cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    return {
        "wi": init_dense(ks[0], d, f, dt),
        "wg": init_dense(ks[1], d, f, dt),
        "wo": init_dense(ks[2], f, d, dt),
    }


@jax.named_scope("mlp")
def mlp(p, cfg, x):
    h = dense(x, p["wi"])
    gate = dense(x, p["wg"])
    if cfg.act == "geglu":
        h = jax.nn.gelu(gate, approximate=True) * h
    else:  # swiglu
        h = jax.nn.silu(gate) * h
    return dense(h, p["wo"])
